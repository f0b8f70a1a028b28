"""Ahead-of-time compiles of the main path's kernels for a described
TPU v5e: what the chip's compiler would refuse fails here, at no chip
time.  Nothing runs, so nothing here says anything about results.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
xdist worker imports this file.  The persistent compilation cache is
off around these compiles (an entry compiled for a described chip
cannot be read back without one).  Shapes come from the host planners
run on real encoded streams at 1M values.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

N = 1 << 20  # values per kernel call: a full-size page batch


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this image
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(a, sharding):
    a = np.asarray(a)
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _compile(fn, *args, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()
    return compiled.as_text()


def _hybrid_plan(width: int, count: int = N):
    from tpuparquet.cpu.dictionary import encode_dict_indices
    from tpuparquet.kernels.hybrid import plan_hybrid

    rng = np.random.default_rng(width)
    # runs of repeats between random stretches: both RLE and bit-packed
    # runs, as a real dictionary-index page has
    idx = rng.integers(0, 1 << width, size=count, dtype=np.uint32)
    idx[: count // 4] = 1
    stream = encode_dict_indices(idx, 1 << width)
    assert stream[0] == width
    return plan_hybrid(stream[1:], count, width)


@pytest.mark.parametrize("width", [3, 17])
def test_expand_hybrid_core(one_chip, width):
    from tpuparquet.kernels.hybrid import expand_hybrid_core, pad_plan

    arrays, cnt, w, n_bp = pad_plan(_hybrid_plan(width))
    idx = jax.ShapeDtypeStruct((cnt,), jnp.int32, sharding=one_chip)
    text = _compile(expand_hybrid_core,
                    *[_spec(a, one_chip) for a in arrays], idx,
                    width=w, n_bp=n_bp)
    assert "HloModule" in text


def test_dict_gather_fixed(one_chip):
    from tpuparquet.kernels.decode import dict_gather_fixed

    lanes = 2
    dictionary = jax.ShapeDtypeStruct((4096 * lanes,), jnp.uint32,
                                      sharding=one_chip)
    indices = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    _compile(dict_gather_fixed, dictionary, indices, lanes=lanes)


def test_expand_delta_i64(one_chip):
    """At 64K values: the 64-bit associative scan takes the TPU compiler
    over a minute at 1M (an open question in PERF.md), too long for a
    tier-1 test."""
    from tpuparquet.cpu.delta import encode_delta_binary_packed
    from tpuparquet.kernels.decode import (DeltaPlan, expand_delta_i64,
                                           plan_delta_i64)

    rng = np.random.default_rng(1)
    n = 1 << 16
    ts = 1_700_000_000_000 + rng.integers(0, 3_600_000, size=n).cumsum()
    plan = plan_delta_i64(np.frombuffer(
        encode_delta_binary_packed(ts.astype(np.int64)), dtype=np.uint8))
    # every host array of the plan becomes an argument of the program
    host = [(g[1], g[2], g[3]) for g in plan.groups]

    def fn(md_lo, md_hi, *flat):
        groups = []
        for k, g in enumerate(plan.groups):
            words, starts, takes = flat[3 * k: 3 * k + 3]
            groups.append((g[0], words,
                           None if g[2] is None else starts,
                           None if g[3] is None else takes,
                           g[4], g[5], g[6]))
        return expand_delta_i64(DeltaPlan(groups, md_lo, md_hi,
                                          plan.block_size, plan.first,
                                          plan.total))

    args = [_spec(plan.md_lo, one_chip), _spec(plan.md_hi, one_chip)]
    for words, starts, takes in host:
        args.append(_spec(words, one_chip))
        for a in (starts, takes):
            args.append(_spec(np.zeros(1, np.int32) if a is None else a,
                              one_chip))
    _compile(fn, *args)


def test_levels_to_validity_and_scatter(one_chip):
    from tpuparquet.kernels.decode import (levels_to_validity,
                                           scatter_to_dense)

    lanes = 2
    dl = jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)
    packed = jax.ShapeDtypeStruct((N * lanes,), jnp.uint32,
                                  sharding=one_chip)

    def fn(def_levels, packed):
        mask, pos = levels_to_validity(def_levels, max_def=1)
        return scatter_to_dense(packed, mask, pos, lanes=lanes)

    _compile(fn, dl, packed)


@pytest.mark.parametrize("kind", ["dict", "dict_bytes", "plain"])
def test_chunk_program(one_chip, kind):
    """One column chunk of a 1,048,576-row row group in one program:
    52 pages of 20,000 rows (a group of 56 slots) and a short last
    page, nullable, as a TLC taxi month's chunks are."""
    from tpuparquet.kernels.decode import chunk_program

    u32 = jnp.uint32

    def arr(shape, dtype=u32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def stream(slots, cnt, w, runs):
        return arr((slots, cnt // 32 * w)), arr((slots, 4, runs))

    lev = (stream(56, 32768, 1, 32), stream(1, 16384, 1, 32))
    lev_sig = ((32768, 1, 32768, True), (16384, 1, 16384, True))
    lanes, shared = 2, (arr((1024,)),)
    if kind == "dict":
        val = (stream(56, 32768, 9, 64), stream(1, 16384, 9, 64))
        val_sig = (("dict", 32768, 9, 32768, False),
                   ("dict", 16384, 9, 16384, False))
    elif kind == "dict_bytes":
        lanes, shared = 1, (arr((32,), jnp.int32), arr((32,), jnp.uint8))
        val = (stream(56, 32768, 1, 32), stream(1, 16384, 1, 32))
        val_sig = (("dict_bytes", 32768, 1, 32768, True, 32768, 0),
                   ("dict_bytes", 16384, 1, 16384, True, 16384, 0))
    else:
        val = ((arr((56, 40960)),), (arr((1, 32768)),))
        val_sig = (("plain",), ("plain",))
    meta = arr((114, 5), jnp.int32)
    sig = (lev_sig, val_sig, N, N * lanes, lanes, 1)
    _compile(chunk_program, shared, lev, val, meta, sig=sig)


@pytest.mark.parametrize("kind", ["plain_bytes", "planes", "delta"])
def test_chunk_program_lineitem(one_chip, kind):
    """A required TPC-H lineitem chunk of a 1,048,576-row row group:
    pages of 20,000 rows after a dictionary fills, as ``l_comment``
    (PLAIN bytes), ``l_partkey`` (byte planes) and ``l_orderkey``
    (delta lanes) write them."""
    from tpuparquet.kernels.decode import chunk_program

    u32, u8, i32 = jnp.uint32, jnp.uint8, jnp.int32

    def arr(shape, dtype=u32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lanes, shared = 2, ()
    if kind == "plain_bytes":
        lanes = 1
        shared = (arr((32768,), i32), arr((1 << 20,), u8))
        val = ((arr((2, 4096 * 17 // 32)), arr((2, 4, 32))),
               (arr((56, 540672), u8),), (arr((1, 139264), u8),))
        val_sig = (("dict_bytes", 4096, 17, 4096, True, 1 << 17, 0),
                   ("plain_bytes",), ("plain_bytes",))
        total = 1 << 25
    elif kind == "planes":
        spec = (("bytes", ("raw8", 0), ("raw8", 1), ("raw8", 2),
                 ("rle8", 0, 32)), ("rle32", 0, 32))

        def planes(slots, stride):
            return (arr((slots, 1)), arr((slots, 32), i32),
                    arr((slots, 32)), arr((slots, 3 * stride), u8),
                    arr((slots, 32), i32), arr((slots, 32), u8))

        val = (planes(48, 20000), planes(1, 8576))
        val_sig = (("planes", spec, 20000), ("planes", spec, 8576))
        total = N * lanes
    else:
        def delta(slots):
            return (arr((slots, 32768 // 32 * 5)), arr((slots, 1)),
                    arr((slots, 1)))

        val = (delta(32), delta(1))
        val_sig = (("delta", 32768, 5, True), ("delta", 32768, 4, True))
        total = N * lanes
    meta = arr((sum(g[0].shape[0] for g in val), 5), i32)
    sig = ((), val_sig, 0, total, lanes, 0)
    _compile(chunk_program, shared, (), val, meta, sig=sig)


# lineitem string chunks of a 1,048,576-row group: (dictionary entries,
# their bytes, index width, fixed entry length or 0, per group its page
# slots, index count and byte cap)
DICT_BYTES = {
    "shipinstruct-256Ki": (4, 48, 2, 0, ((56, 32768, 1 << 18),
                                         (1, 16384, 1 << 17))),
    "comment-1Mi": (38_000, 1 << 20, 16, 0, ((1, 32768, 1 << 20),
                                             (1, 32768, 1 << 19))),
    "flag-fixed": (3, 3, 2, 1, ((56, 32768, 32768), (1, 16384, 16384))),
}


@pytest.mark.parametrize("shape", list(DICT_BYTES))
def test_chunk_program_dict_bytes_lineitem(one_chip, shape):
    """A lineitem string chunk's ``"dict_bytes"`` groups in one program:
    ``l_shipinstruct`` (four entries of 4-17 bytes), ``l_comment``'s
    two dictionary pages before its PLAIN fallback, and ``l_returnflag``
    (one-byte entries, the fixed-length row gather).  Each must compile
    in seconds: the byte lookup is a blocked running count, because one
    ``cumsum`` over a million values, a ``reduce_window`` on the TPU,
    took its compiler about 29 s (``decode._running_count``)."""
    import time

    from tpuparquet.kernels.decode import bucket, chunk_program

    u32, u8, i32 = jnp.uint32, jnp.uint8, jnp.int32

    def arr(shape, dtype=u32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n_dict, n_bytes, w, fixed, groups = DICT_BYTES[shape]
    shared = (arr((bucket(n_dict + 1),), i32), arr((bucket(n_bytes),), u8))
    val = tuple((arr((slots, cnt // 32 * w)), arr((slots, 4, 32)))
                for slots, cnt, _ in groups)
    val_sig = tuple(("dict_bytes", cnt, w, cnt, True, cap, fixed)
                    for _, cnt, cap in groups)
    total = bucket(sum(slots * cap for slots, _, cap in groups))
    meta = arr((sum(slots for slots, _, _ in groups), 5), i32)
    t0 = time.perf_counter()
    _compile(chunk_program, shared, (), val, meta,
             sig=((), val_sig, 0, total, 1, 0))
    assert time.perf_counter() - t0 < 30


def test_expand_tokens(one_chip):
    from tpuparquet.kernels.snappy import expand_tokens

    n_tok = 1 << 16
    te = jax.ShapeDtypeStruct((n_tok,), jnp.int32, sharding=one_chip)
    ts = jax.ShapeDtypeStruct((n_tok,), jnp.int32, sharding=one_chip)
    lits = jax.ShapeDtypeStruct((1 << 18,), jnp.uint8, sharding=one_chip)
    _compile(expand_tokens, te, ts, lits, out_cap=N, steps=20)


@pytest.mark.parametrize("width", [1, 17, 32])
def test_unpack_u32_pallas_reaches_mosaic(one_chip, width):
    """interpret=False must lower through Mosaic for the chip: a kernel
    that quietly fell back to the interpreter would show no custom
    call."""
    from tpuparquet.kernels.bitunpack import unpack_u32_pallas

    words = jax.ShapeDtypeStruct((N // 32, width), jnp.uint32,
                                 sharding=one_chip)
    text = jax.jit(unpack_u32_pallas,
                   static_argnames=("width", "count", "interpret")).lower(
        words, width=width, count=N, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


def test_decode_step_spmd_all_gathers(topo):
    """The SPMD decode step on four described chips: sharded unit-wise
    over "rg", and the compiler must put in the all-gathers."""
    from tpuparquet.shard.mesh import (decode_step_spmd, make_mesh,
                                       stack_hybrid_plans)

    mesh = make_mesh(devices=topo.devices)
    assert mesh.devices.size == 4
    plans = [_hybrid_plan(8, count=N // 8) for _ in range(8)]
    batch = stack_hybrid_plans(plans, n_units=8)
    lanes = 2
    step = decode_step_spmd(mesh, batch.count, batch.width, batch.n_bp,
                            lanes)
    unit = NamedSharding(mesh, P("rg"))
    rep = NamedSharding(mesh, P())
    args = [_spec(a, unit) for a in batch.arrays()]
    dictionary = jax.ShapeDtypeStruct((1 << 8, lanes), jnp.uint32,
                                      sharding=rep)
    compiled = step.lower(*args, dictionary).compile()
    assert "all-gather" in compiled.as_text()
    per_device = compiled.memory_analysis()
    assert per_device is not None
