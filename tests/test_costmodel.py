"""Static cost-model ledger (`lightgbm_tpu/analysis/costmodel.py`).

Covers the pass from both sides, mirroring test_analysis.py:

  * seeded regressions TRIP the gate — a doctored pin (2x FLOPs, halved
    bytes, a phantom collective payload) produces a ``cost-regression``
    finding that names the program, the metric, pinned vs measured and
    the heaviest jaxpr region; a missing pin is ``cost-unpinned``; a pin
    for a removed program is ``cost-stale-pin``;
  * tolerance bands are exact at the edges (two-sided, relative);
  * ``--dump-costs`` is byte-identical against the checked-in
    ``analysis/costs.json`` under the production x64-off config — i.e.
    the repo's pins are CURRENT, and re-deriving them is reproducible.

The in-process tests derive their pins from the same in-process
measurement (the test suite runs x64 ON, the production gate x64 OFF —
absolute pins only hold in a gate-config subprocess).
"""

import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.analysis import costmodel, jaxpr_lint
from lightgbm_tpu.analysis.common import COSTS_PATH

pytestmark = pytest.mark.analysis

_HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(_HERE)


def _toy_closed():
    return jax.make_jaxpr(lambda x: jnp.dot(x, x) + 1.0)(
        jnp.ones((64, 64), jnp.float32))


@pytest.fixture(scope="module")
def serving_bin():
    """One shared trace + measurement of the cheapest real program."""
    traced = jaxpr_lint.trace_programs(glob="serving_bin")
    closed = traced.closed["serving_bin"]
    return closed, costmodel.measure(closed)


# -- measurement -------------------------------------------------------------

def test_measure_toy_program_metrics():
    closed = _toy_closed()
    m = costmodel.measure(closed)
    # XLA's analytical model: a 64x64 f32 matmul is ~2*64^3 flops
    assert m["flops"] >= 64 ** 3
    assert m["bytes_accessed"] >= 2 * 64 * 64 * 4
    # liveness peak covers at least the input + one live output buffer
    assert m["peak_live_bytes"] >= 2 * 64 * 64 * 4
    assert m["exchange_bytes"] == {}          # collective-free program
    assert m["eqns"] >= 1
    # deterministic: same jaxpr, same ledger row (what makes pins pinnable)
    assert costmodel.measure(closed) == m


def test_peak_live_bytes_liveness_walk():
    # x (4 KB) is dead after the add: at the mul, live = temp + out
    closed = jax.make_jaxpr(lambda x: (x + 1.0) * 2.0)(
        jnp.ones(1024, jnp.float32))
    assert costmodel.peak_live_bytes(closed.jaxpr) == 2 * 4096


def test_exchange_bytes_on_psum_program():
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(2)
    kw = dict(mesh=mesh, in_specs=(P("data"),), out_specs=P())
    body = lambda x: lax.psum(x, "data")  # noqa: E731
    fn = jax.shard_map(body, check_vma=False, **kw)
    closed = jax.make_jaxpr(fn)(jnp.ones(8, jnp.float32))
    ex = costmodel.measure(closed)["exchange_bytes"]
    assert ex.get("psum", 0) > 0


# -- seeded regressions trip the gate ----------------------------------------

def _entry(row):
    return {"flops": row["flops"], "bytes_accessed": row["bytes_accessed"],
            "peak_live_bytes": row["peak_live_bytes"],
            "exchange_bytes": dict(row["exchange_bytes"])}


def test_matching_pin_is_green(serving_bin):
    closed, row = serving_bin
    fs = costmodel.check_costs("serving_bin", closed, _entry(row),
                               dict(costmodel.DEFAULT_TOLERANCE),
                               measured=row)
    assert fs == [], [str(f) for f in fs]


def test_doctored_flop_pin_trips_with_forensics(serving_bin):
    closed, row = serving_bin
    bad = dict(_entry(row), flops=row["flops"] * 2)
    fs = costmodel.check_costs("serving_bin", closed, bad,
                               dict(costmodel.DEFAULT_TOLERANCE),
                               measured=row)
    assert len(fs) == 1 and fs[0].rule == "cost-regression"
    # the finding carries everything a reviewer needs: program, metric,
    # both values, the offending jaxpr region, and the re-pin workflow
    assert fs[0].symbol == "serving_bin"
    assert fs[0].file == "lightgbm_tpu/serving/binner.py"
    msg = fs[0].message
    assert "flops" in msg and str(row["flops"]) in msg \
        and str(row["flops"] * 2) in msg
    assert "below the band" in msg
    assert "heaviest region" in msg and "--dump-costs" in msg


def test_doctored_bytes_pin_trips_above_band(serving_bin):
    closed, row = serving_bin
    low = dict(_entry(row),
               bytes_accessed=max(1, row["bytes_accessed"] // 2))
    fs = costmodel.check_costs("serving_bin", closed, low,
                               dict(costmodel.DEFAULT_TOLERANCE),
                               measured=row)
    assert [f.rule for f in fs] == ["cost-regression"]
    assert "bytes_accessed" in fs[0].message
    assert "above the band" in fs[0].message


def test_phantom_collective_payload_trips(serving_bin):
    # exchange payloads carry ZERO tolerance: a pinned collective the
    # program no longer performs (or a new one it silently grew) fails
    closed, row = serving_bin
    ex = dict(_entry(row), exchange_bytes={"psum": 1024})
    fs = costmodel.check_costs("serving_bin", closed, ex,
                               dict(costmodel.DEFAULT_TOLERANCE),
                               measured=row)
    assert len(fs) == 1 and fs[0].rule == "cost-regression"
    assert "exchange_bytes[psum]" in fs[0].message


def test_unpinned_program_and_missing_metric(serving_bin):
    closed, row = serving_bin
    fs = costmodel.check_costs("serving_bin", closed, {},
                               dict(costmodel.DEFAULT_TOLERANCE),
                               measured=row)
    assert [f.rule for f in fs] == ["cost-unpinned"]
    partial = _entry(row)
    del partial["peak_live_bytes"]
    fs = costmodel.check_costs("serving_bin", closed, partial,
                               dict(costmodel.DEFAULT_TOLERANCE),
                               measured=row)
    assert [f.rule for f in fs] == ["cost-unpinned"]
    assert "peak_live_bytes" in fs[0].message


def test_stale_pin_for_removed_program():
    tp = jaxpr_lint.TracedPrograms()           # nothing traced
    costs = {"tolerance": {}, "programs": {"ghost": {"flops": 1}}}
    fs, measured, skipped = costmodel.run(costs=costs, traced=tp)
    assert measured == {}
    assert [f.rule for f in fs] == ["cost-stale-pin"]
    assert fs[0].symbol == "ghost"
    assert fs[0].file == "analysis/costs.json"


def test_gate_exits_nonzero_on_seeded_cost_regression(serving_bin,
                                                      monkeypatch):
    """The CLI gate path end to end (in-process): a doctored ledger makes
    `--passes costmodel` exit 1; the honest ledger row exits 0."""
    from lightgbm_tpu.analysis import __main__ as gate

    closed, row = serving_bin
    good = {"tolerance": dict(costmodel.DEFAULT_TOLERANCE),
            "programs": {"serving_bin": _entry(row)}}
    bad = {"tolerance": dict(costmodel.DEFAULT_TOLERANCE),
           "programs": {"serving_bin": dict(_entry(row),
                                            flops=row["flops"] * 2)}}
    argv = ["--passes", "costmodel", "--programs", "serving_bin", "--quiet"]
    monkeypatch.setattr(costmodel, "load_costs", lambda: good)
    assert gate.main(argv) == 0
    monkeypatch.setattr(costmodel, "load_costs", lambda: bad)
    assert gate.main(argv) == 1


# -- tolerance-band edges ----------------------------------------------------

def test_tolerance_band_edges():
    closed = _toy_closed()

    def check(pinned, measured, tol):
        return costmodel._check_scalar("toy", "flops", pinned, measured,
                                       tol, closed, "toy.py")

    assert check(100, 110, 0.10) is None       # exactly on the band: ok
    assert check(100, 90, 0.10) is None
    assert check(100, 111, 0.10) is not None   # one past, either side
    assert check(100, 89, 0.10) is not None
    assert check(100, 100, 0.0) is None        # zero tolerance = exact
    assert check(100, 101, 0.0) is not None


def test_default_tolerance_shape():
    assert set(costmodel.DEFAULT_TOLERANCE) == set(costmodel.METRICS)
    # the collective payload contract is exact by default
    assert costmodel.DEFAULT_TOLERANCE["exchange_bytes"] == 0.0


# -- the checked-in ledger is current + --dump-costs is byte-identical -------

@pytest.mark.analysis(timeout=300)
def test_dump_costs_byte_identical_and_pins_current(tmp_path):
    """`--dump-costs` under the production gate config (x64 off, 8-way
    CPU) re-derives EXACTLY the checked-in analysis/costs.json — the
    pins are current and the dump is reproducible, byte for byte."""
    out = tmp_path / "costs.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_ENABLE_X64", None)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # JAX_COMPILATION_CACHE_DIR is inherited (conftest.py places it)
    proc = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu.analysis",
         "--dump-costs", str(out), "--quiet"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert out.read_bytes() == open(COSTS_PATH, "rb").read()
