"""ops/select.py: the counting select equals ``np.sort``'s order
statistic bit for bit, for every k, on the inputs GOSS hands it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from lightgbm_tpu.ops.select import (PASSES, _PASSES, kth_largest,
                                     kth_smallest)

N = 4000


def _inputs():
    rng = np.random.default_rng(27)
    u = rng.random(N, dtype=np.float32)
    tied = np.round(rng.random(N) * 6).astype(np.float32) / 8
    # 60% of the rows share ONE value that sits mid-range: whatever k
    # falls among them has its threshold inside the tie
    heavy = np.where(rng.random(N) < 0.6, np.float32(0.3125),
                     rng.random(N, dtype=np.float32))
    masked = np.where(rng.random(N) < 0.7, u, np.inf).astype(np.float32)
    tiny = (rng.integers(0, 1 << 12, N).astype(np.uint32)
            * np.uint32(2047)).view(np.float32)      # all below 2^-126
    assert np.all(tiny < np.finfo(np.float32).tiny)
    mixed = np.concatenate([tiny[:N // 2], u[:N // 4],
                            np.zeros(N // 4, np.float32)])
    gh = np.abs(rng.normal(size=N) * rng.random(N)).astype(np.float32)
    gh[-37:] = 0.0                                   # padding rows
    return {"uniform": u, "all_equal": np.full(N, 0.625, np.float32),
            "all_zero": np.zeros(N, np.float32), "coarse_ties": tied,
            "heavy_tie": heavy, "inf_tail": masked,
            "all_inf": np.full(N, np.inf, np.float32),
            "denormal": tiny, "denormal_mixed": mixed, "abs_gh": gh,
            "odd_length": u[:1237], "one_row": u[:1]}


INPUTS = _inputs()


def _ks(n):
    return sorted({min(k, n) for k in
                   (1, 2, max(n // 5, 1), max(n // 2, 1), max(n - 1, 1), n)})


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


def _shard_case():
    """A different k on every shard of the 8 fake devices; the lowered
    program must hold no collective (counts are per-shard sums)."""
    from lightgbm_tpu.parallel.mesh import create_data_mesh, shard_map
    mesh = create_data_mesh()
    d = mesh.devices.size
    x = np.concatenate([INPUTS["heavy_tie"], INPUTS["inf_tail"]])
    per = len(x) // d
    ks = np.asarray([1, 2, per // 5, per // 2, per - 1, per, 7, 300][:d],
                    np.int32)

    def local(xs, tbl):
        k = tbl[jax.lax.axis_index("data")]
        return jnp.stack([kth_largest(xs, k), kth_smallest(xs, k)])[None]

    fn = jax.jit(shard_map(local, mesh=mesh, in_specs=(P("data"), P()),
                           out_specs=P("data", None), check_vma=False))
    xd = jax.device_put(x, NamedSharding(mesh, P("data")))
    text = fn.lower(xd, ks).compile().as_text()
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute", "reduce-scatter"):
        assert collective not in text, collective
    got = np.asarray(fn(xd, ks))
    for s in range(d):
        srt = np.sort(x[s * per:(s + 1) * per])
        want = np.asarray([srt[per - ks[s]], srt[ks[s] - 1]])
        np.testing.assert_array_equal(_bits(got[s]), _bits(want))


@pytest.mark.parametrize("case", sorted(INPUTS) + ["traced_k", "clipped_k",
                                                   "shard_map"])
def test_counting_select_equals_sort(case):
    if case == "shard_map":
        return _shard_case()
    x = INPUTS["coarse_ties" if case in ("traced_k", "clipped_k") else case]
    n = len(x)
    srt = np.sort(x)
    if case == "clipped_k":
        # out-of-range k reads the nearest end, as the sort's clipped
        # index did (k_cap = 0 in goss_masks)
        ks, want_k = [0, -3, n + 1, 2 * n], [1, 1, n, n]
    else:
        ks = want_k = _ks(n)
    if case == "traced_k":
        # ONE compiled program serves every k
        large = jax.jit(kth_largest)
        small = jax.jit(kth_smallest)
        ks = [jnp.int32(k) for k in ks]
    else:
        large, small = kth_largest, kth_smallest
    xd = jnp.asarray(x)
    for k, wk in zip(ks, want_k):
        np.testing.assert_array_equal(
            _bits(large(xd, k)), _bits(srt[n - wk]), err_msg=f"largest {k}")
        np.testing.assert_array_equal(
            _bits(small(xd, k)), _bits(srt[wk - 1]), err_msg=f"smallest {k}")
    if case == "traced_k":
        assert large._cache_size() == 1 and small._cache_size() == 1


def test_passes_cover_the_31_value_bits():
    """Every value bit is settled once, and each pass compares and counts
    the rows once a candidate digit (XLA fuses a pass's counts into one
    read on the chip: tests/test_chip_compile.py)."""
    assert sum(b for _, b in _PASSES) == 31 and len(_PASSES) == PASSES
    jaxpr = jax.make_jaxpr(kth_largest)(jnp.zeros(64, jnp.float32), 3)
    counts = [e for e in jaxpr.eqns if e.primitive.name == "reduce_sum"]
    assert len(counts) == sum((1 << b) - 1 for _, b in _PASSES)
