"""The static contract checker, turned on itself.

Four suites:

* **Seeded lint violations** -- every layer-2 rule is demonstrated by a
  deliberately-broken construct in ``tests/_bad_kernels.py`` (linted
  under a pretend in-tree path so path-scoped rules apply); a rule that
  stops firing on its seeded line is a rule that rotted.
* **Waivers** -- a ``# verify: allow(rule)`` comment downgrades the
  violation to a reported waiver, on the line or on the enclosing def.
* **Interval engine** -- a Pallas kernel with a provably in-bounds
  store passes; an out-of-bounds twin is flagged as a violation.
* **VC differential (fuzz satellite)** -- ``_fuzz.perturb_plan`` twins
  (capacity below nnz_c, halved hash tables) are rejected by
  :func:`repro.verify.check_plan_vcs` while the untouched plan passes.

The live-tree gate (``python -m repro.verify --all``) runs in CI; here
``test_repo_surface_is_lint_clean`` pins the layer-2 half so a plain
pytest run also catches regressions.
"""
import pathlib
import re

import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from _fuzz import PLAN_PERTURBATIONS, csr_of, perturb_plan, rand_dense
from repro.core import plan_spgemm
from repro.verify import (JaxprAnalyzer, check_plan_vcs,
                          run_layer2, verify_spgemm)
from repro.verify.intervals import Ival, VIOLATION
from repro.verify.lint import lint_source

ROOT = pathlib.Path(__file__).resolve().parents[1]
BAD_PATH = ROOT / "tests" / "_bad_kernels.py"
#: pretend in-tree location: inside src/repro, core/, and kernels/, so
#: every path-scoped rule is in scope for the seeded fixture
FAKE_PATH = "src/repro/core/kernels/_bad.py"


def _seeded_lines():
    """rule name -> sorted list of ``# BAD:`` line numbers in the fixture."""
    marks = {}
    for lineno, text in enumerate(BAD_PATH.read_text().splitlines(), 1):
        m = re.search(r"#\s*BAD:\s*([a-z-]+)", text)
        if m:
            marks.setdefault(m.group(1), []).append(lineno)
    return marks


def test_every_rule_has_a_seeded_violation():
    import repro.verify.rules  # noqa: F401  (registers the rule set)
    from repro.verify.lint import rule_names
    marks = _seeded_lines()
    assert set(marks) == set(rule_names()), \
        "every registered rule needs a # BAD: line in _bad_kernels.py"
    assert len(marks) >= 6


def test_seeded_violations_all_fire_on_their_lines():
    violations, waivers = lint_source(BAD_PATH.read_text(), FAKE_PATH)
    assert not waivers
    got = {}
    for v in violations:
        got.setdefault(v.rule, set()).add(v.line)
    for rule, lines in _seeded_lines().items():
        assert rule in got, f"rule {rule} never fired on the fixture"
        assert got[rule] == set(lines), \
            f"{rule}: fired on {sorted(got[rule])}, seeded {lines}"
    # and nothing fired on an unmarked line
    marked = {ln for lines in _seeded_lines().values() for ln in lines}
    stray = {(v.rule, v.line) for v in violations if v.line not in marked}
    assert not stray, f"unseeded findings: {stray}"


def test_waiver_comment_downgrades_to_reported_waiver():
    src = ("def f(c):\n"
           "    return c.to_dense()  # verify: allow(no-densify)\n")
    violations, waivers = lint_source(src, FAKE_PATH, ["no-densify"])
    assert not violations
    assert [w.rule for w in waivers] == ["no-densify"]

    # a waiver on the enclosing def line covers the whole body
    src = ("def f(c):  # verify: allow(no-densify)\n"
           "    return c.to_dense()\n")
    violations, waivers = lint_source(src, FAKE_PATH, ["no-densify"])
    assert not violations and len(waivers) == 1

    # but a waiver for a *different* rule suppresses nothing
    src = ("def f(c):\n"
           "    return c.to_dense()  # verify: allow(counter-reset)\n")
    violations, _ = lint_source(src, FAKE_PATH, ["no-densify"])
    assert len(violations) == 1


def test_bad_fixture_is_excluded_from_the_ci_surface():
    from repro.verify.lint import default_paths
    assert not any(p.endswith("_bad_kernels.py")
                   for p in default_paths(str(ROOT)))


def test_repo_surface_is_lint_clean():
    violations, _waivers, n_files = run_layer2(str(ROOT))
    assert n_files > 50
    assert violations == [], "\n".join(str(v) for v in violations)


# ---------------------------------------------------------------------------
# interval engine on hand-built Pallas kernels
# ---------------------------------------------------------------------------

def _analyze_kernel(kernel, grid, out_len):
    fn = pl.pallas_call(
        kernel, grid=(grid,),
        out_shape=jax.ShapeDtypeStruct((out_len,), jnp.float32))
    cj = jax.make_jaxpr(fn)()
    analyzer = JaxprAnalyzer()
    analyzer.analyze(cj, [])
    return analyzer


def test_interval_engine_proves_in_bounds_store():
    def ok_kernel(o_ref):
        i = pl.program_id(0)
        o_ref[i] = 1.0

    analyzer = _analyze_kernel(ok_kernel, grid=4, out_len=8)
    assert not [s for s in analyzer.sites if s.status == VIOLATION]
    assert any(s.status == "proved" for s in analyzer.sites)


def test_interval_engine_flags_out_of_bounds_store():
    def oob_kernel(o_ref):
        i = pl.program_id(0)
        o_ref[i + 8] = 1.0      # i in [0, 3] -> index in [8, 11], len 8

    analyzer = _analyze_kernel(oob_kernel, grid=4, out_len=8)
    bad = [s for s in analyzer.sites if s.status == VIOLATION]
    assert bad, "out-of-bounds store must be a violation"
    assert bad[0].index == (8, 11)


@pytest.mark.parametrize("offset", (0, 8), ids=("in_bounds", "out_of_bounds"))
def test_interval_engine_reads_masked_store_indices(offset):
    """A masked store (``pltpu.store(..., mask=)``, the ``masked_swap``
    primitive) flattens its indexers with the value and the mask; its
    slice start is checked like a plain store's."""
    from jax.experimental.pallas import tpu as pltpu

    def kernel(o_ref):
        i = pl.program_id(0)
        pltpu.store(o_ref.at[pl.ds(i + offset, 1)],
                    jnp.ones((1,), jnp.float32),
                    mask=jnp.ones((1,), jnp.bool_))

    analyzer = _analyze_kernel(kernel, grid=4, out_len=8)
    bad = [s for s in analyzer.sites if s.status == VIOLATION]
    if offset:
        assert [s.index for s in bad] == [(8, 11)]
    else:
        assert not bad
        assert any(s.status == "proved" for s in analyzer.sites)


def test_ival_arithmetic_basics():
    a, b = Ival(0, 3), Ival(2, 5)
    assert a.join(b).lo == 0 and a.join(b).hi == 5
    assert a.within(0, 3) and not b.within(0, 3)


# ---------------------------------------------------------------------------
# VC differential: perturbed frozen plans must be rejected (fuzz satellite)
# ---------------------------------------------------------------------------

def _hash_plan():
    a = csr_of(rand_dense(12, 10, 0.4, 11))
    b = csr_of(rand_dense(10, 9, 0.4, 12))
    return plan_spgemm(a, b, algorithm="hash", cache=False), a, b


@pytest.mark.parametrize("which", PLAN_PERTURBATIONS)
def test_perturbed_plan_rejected_untouched_passes(which):
    plan, _a, _b = _hash_plan()
    assert all(vc.ok for vc in check_plan_vcs(plan)), \
        "the untouched plan must verify clean"
    bad = perturb_plan(plan, which)
    failed = [vc.name for vc in check_plan_vcs(bad) if not vc.ok]
    assert failed, f"perturbation {which!r} was not rejected"
    # perturb_plan returns a twin; the original still verifies
    assert all(vc.ok for vc in check_plan_vcs(plan))


def test_cap_perturbation_fails_capacity_vcs():
    plan, _a, _b = _hash_plan()
    failed = {vc.name for vc in check_plan_vcs(perturb_plan(plan, "cap_c"))
              if not vc.ok}
    assert {"nnz-consistent", "store-capacity"} & failed


def test_verify_spgemm_end_to_end_clean():
    plan, a, b = _hash_plan()
    case = verify_spgemm(plan, a, b)
    assert case.ok, (case.violations,
                     [vc for vc in case.vcs if not vc.ok], case.budget)
    assert not case.violations
    assert case.site_counts.get("proved", 0) > 0
    assert case.budget["got"]["pallas_call"] == 1


def test_verify_spgemm_catches_perturbed_schedule():
    plan, a, b = _hash_plan()
    case = verify_spgemm(perturb_plan(plan, "bin_tsize"), a, b,
                         name="spgemm/seeded-bad")
    assert not case.ok
    assert any(not vc.ok for vc in case.vcs)
