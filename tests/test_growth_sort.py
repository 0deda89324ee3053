"""``learner_wave.growth_sort`` past ``_SORT_MAX_OPERANDS``: a wide table's
words go through one compiled sort of a chunk at a time, and the result is
the one sort's, to the bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from lightgbm_tpu import learner_wave
from lightgbm_tpu.learner_wave import growth_sort, growth_sort_operands


def _state(fw, n=4096, seed=0):
    rng = np.random.RandomState(seed)
    key = jnp.asarray(2 * rng.randint(0, 64, n), jnp.int32)
    bins = jnp.asarray(rng.randint(-2**31, 2**31 - 1, (fw, n)), jnp.int32)
    w = jnp.asarray(np.stack([rng.randn(n), rng.rand(n),
                              rng.rand(n) > 0.3]).astype(np.float32))
    rid = jnp.asarray(rng.permutation(n), jnp.int32)
    lid = jnp.asarray(rng.randint(0, 509, n), jnp.int32)
    return key, bins, w, rid, lid


def _one_sort(key, bins, w, rid, lid):
    """The single sort of every operand, written out."""
    fw = bins.shape[0]
    lid_bag = lid | ((w[2] > 0.5).astype(jnp.int32) << 30)
    sd = lax.sort([key, rid] + [bins[i] for i in range(fw)]
                  + [w[0], w[1], lid_bag], num_keys=2, is_stable=False)
    return (sd[0], jnp.stack(sd[2:2 + fw]),
            jnp.stack([sd[2 + fw], sd[3 + fw],
                       (sd[4 + fw] >> 30).astype(jnp.float32)]),
            sd[1], sd[4 + fw] & ((1 << 30) - 1))


@pytest.mark.parametrize("fw", [56, 22, 64])
def test_chunked_growth_sort_is_the_one_sort(fw):
    assert growth_sort_operands(fw) > learner_wave._SORT_MAX_OPERANDS
    st = _state(fw)
    with jax.enable_x64(False):
        got = jax.jit(lambda *a: growth_sort(*a, 509))(*st)
        want = jax.jit(_one_sort)(*st)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_cells_widths_keep_the_one_sort():
    """Higgs's 7 words and the four-chip cell's 18 sort in one sort: their
    programs are the ones every earlier reading was taken on."""
    for fw in (7, 8, 18):
        st = _state(fw)
        with jax.enable_x64(False):
            jx = jax.make_jaxpr(lambda *a: growth_sort(*a, 509))(*st)
        sorts = [e for e in jx.jaxpr.eqns if e.primitive.name == "sort"]
        assert len(sorts) == 1
        assert len(sorts[0].invars) == growth_sort_operands(fw)


def test_the_budget_reckons_the_chunked_sort_and_takes_the_ranking_cell():
    """The working set reckons what each sort holds: a one-sort table its
    operands in and out, a chunked one its operands, permuted in their own
    buffers, and one chunk's sort in and out (79 of the 81 row-sized
    operands by AOT: ``test_chip_compile.py``).  At the ranking cell
    (7,326,720 rows, 224 padded columns, 255 leaves) that is 3.7 GB, under
    ``tpu_wave_max_bytes``; reckoned as one sort it is 4.8 GB, and the job
    would fall to the sequential learner."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner_wave import (wave_budget_reason,
                                           wave_transient_bytes)
    cfg = Config.from_params({"objective": "lambdarank", "num_leaves": 255,
                              "max_bin": 255})
    n = 7_326_720
    got = wave_transient_bytes(cfg, n, 224, 256)
    assert got["sort_buffer_bytes"] == (61 + 2 * 10) * n * 4
    assert wave_budget_reason(cfg, n, 224, 256) is None
    assert got["total_bytes"] < 4 << 30
    for f_pad in (32, 72):
        assert wave_transient_bytes(cfg, n, f_pad, 256)["sort_buffer_bytes"] \
            == 2 * growth_sort_operands(f_pad // 4) * n * 4


def test_chunked_growth_sort_permutes_the_table_in_its_own_buffer():
    """The chunks are sorted in a loop that carries the whole table and
    writes each sorted chunk over the one it read: what lets the compiler
    keep one table, not two, beside a chunk's sort."""
    st = _state(56)
    with jax.enable_x64(False):
        jx = jax.make_jaxpr(lambda *a: growth_sort(*a, 509))(*st)
    loops = [e for e in jx.jaxpr.eqns
             if e.primitive.name in ("while", "scan")]
    assert len(loops) == 1
    body = (loops[0].params.get("body_jaxpr")
            or loops[0].params["jaxpr"]).jaxpr
    assert [v.aval.shape for v in body.outvars].count((56, 4096)) == 1
    names = [e.primitive.name for e in body.eqns]
    assert names.count("sort") == 1 and "dynamic_update_slice" in names
    sort = next(e for e in body.eqns if e.primitive.name == "sort")
    assert len(sort.invars) == 2 + learner_wave._SORT_CHUNK_WORDS
