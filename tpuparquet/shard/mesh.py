"""Mesh construction and the fully-jittable SPMD decode step.

Two parallel axes, chosen for how parquet decode actually scales
(SURVEY.md §5 "long-context" mapping):

* ``"rg"`` — data parallel over (file × row-group × page) *units*: the
  embarrassingly parallel outer loop of the reference
  (``file_reader.go:51-57``).  Units shard across this axis; no
  communication until the final all-gather of decoded columns.
* ``"sp"`` — sequence parallel over the *value axis within a unit*: each
  shard expands a contiguous slice of output positions from the shared
  run table (the hybrid run structure is random-access after planning, so
  splitting the position axis needs no halo exchange at all).

Both collectives (`all_gather` over "sp" then "rg") ride ICI inside a
slice; across slices XLA places them on DCN — nothing in this module is
topology-specific.

Static-shape discipline: every unit's plan is padded to the batch-wide
bucket (run-count, bp-word-count, value-count), so one compiled program
serves the whole scan regardless of per-page variation.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels.decode import bucket
from ..kernels.hybrid import HybridPlan, expand_hybrid_core, plan_hybrid

__all__ = [
    "make_mesh",
    "assign_units",
    "resolve_out_sharding",
    "placement_devices",
    "dim0_partitions",
    "BatchedHybridPlan",
    "stack_hybrid_plans",
    "decode_step_spmd",
    "sharded_dict_decode",
]


def make_mesh(n_devices: int | None = None, sp: int | None = None,
              devices=None) -> Mesh:
    """Build a ("rg", "sp") mesh over the first ``n_devices`` devices.

    ``sp`` defaults to 2 when the device count is even and >2 (so both
    axes are exercised), else 1 — pass explicitly for real topologies.

    Defaults to this process's LOCAL devices: in a multi-process
    runtime ``jax.devices()`` includes other hosts' non-addressable
    devices, and a scan mesh containing those yields arrays the
    process cannot read (identical to ``jax.devices()`` when
    single-process).  Cross-host layouts pass ``devices`` explicitly.
    """
    devs = list(devices if devices is not None else jax.local_devices())
    if n_devices is not None:
        devs = devs[:n_devices]
    n = len(devs)
    if sp is None:
        sp = 2 if n > 2 and n % 2 == 0 else 1
    if n % sp:
        raise ValueError(f"{n} devices not divisible by sp={sp}")
    arr = np.asarray(devs).reshape(n // sp, sp)
    return Mesh(arr, ("rg", "sp"))


def assign_units(n_units: int, n_shards: int) -> list[list[int]]:
    """Round-robin unit indices over shards (static, deterministic)."""
    out: list[list[int]] = [[] for _ in range(n_shards)]
    for i in range(n_units):
        out[i % n_shards].append(i)
    return out


# ----------------------------------------------------------------------
# Consumer-aligned output placement (the gather-wall fix)
# ----------------------------------------------------------------------

def _gather_to_env():
    """``TPQ_GATHER_TO``: default ``gather_to`` device INDEX (into this
    process's ``jax.local_devices()``) for scans and the free gather
    functions when no explicit placement is passed.  Unset/empty =
    replicated (the seed behavior).  A malformed or out-of-range value
    raises — a placement knob that silently replicated everything
    would defeat its own purpose."""
    raw = os.environ.get("TPQ_GATHER_TO", "")
    if not raw:
        return None
    try:
        idx = int(raw)
    except ValueError:
        raise ValueError(
            f"TPQ_GATHER_TO={raw!r} is not a device index") from None
    devs = jax.local_devices()
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"TPQ_GATHER_TO={idx} out of range: this process has "
            f"{len(devs)} addressable devices")
    return devs[idx]


def resolve_out_sharding(mesh, out_sharding=None, gather_to=None,
                         env_default: bool = True):
    """Resolve a consumer placement request into a ``jax.sharding.
    Sharding`` — or None, meaning the seed's replicate-everywhere
    gather.

    ``out_sharding`` is a ``NamedSharding`` over the CONSUMER's mesh
    (preferred — it carries its own mesh), a bare ``PartitionSpec``
    (interpreted over ``mesh``, the scan's mesh), an already-resolved
    ``NamedSharding``/``SingleDeviceSharding``, or the string
    ``"replicated"`` (the explicit spelling of the seed gather, for
    overriding an armed scan-level/env default).  ``gather_to`` is a
    single target device (a ``jax.Device`` or an index into this
    process's ``jax.local_devices()``) — sugar for a
    ``SingleDeviceSharding``.  At most one may be given; with
    neither, the ``TPQ_GATHER_TO`` env default applies (when
    ``env_default``), else replicated.

    Multi-host semantics: the gather assembles THIS process's decoded
    units on this process's mesh, so the target must be fully
    addressable from this process — each host of a ``MultiHostScan``
    places its own shard of the results (cross-host exchange stays
    with the DCN collectives in ``shard.distributed``).  A target
    naming non-addressable devices is rejected loudly.
    """
    from jax.sharding import SingleDeviceSharding

    if out_sharding is not None and gather_to is not None:
        raise ValueError("pass out_sharding= or gather_to=, not both "
                         "(they are two spellings of one placement)")
    if out_sharding == "replicated":
        # the explicit spelling of the seed replicate-everywhere
        # gather: None cannot express it where a scan-level or env
        # default is armed (None means "use the default" there)
        return None
    if out_sharding is None and gather_to is None:
        if not env_default:
            return None
        gather_to = _gather_to_env()
        if gather_to is None:
            return None
    if gather_to is not None:
        if isinstance(gather_to, int):
            devs = jax.local_devices()
            if not 0 <= gather_to < len(devs):
                raise ValueError(
                    f"gather_to={gather_to} out of range: this process "
                    f"has {len(devs)} addressable devices")
            gather_to = devs[gather_to]
        return SingleDeviceSharding(gather_to)
    if isinstance(out_sharding, P):
        if mesh is None:
            raise ValueError(
                "a bare PartitionSpec has no mesh to bind against "
                "here; pass a NamedSharding over the consumer's mesh")
        try:
            return NamedSharding(mesh, out_sharding)
        except ValueError as e:
            raise ValueError(
                f"out_sharding {out_sharding} does not fit the scan "
                f"mesh (axes {tuple(mesh.axis_names)}): {e}; pass a "
                "NamedSharding over the consumer's mesh to shard "
                "along consumer axes") from e
    if isinstance(out_sharding, jax.sharding.Sharding):
        if not isinstance(out_sharding, (NamedSharding,
                                         SingleDeviceSharding)):
            # the gather's unit-axis padding (dim0_partitions) cannot
            # be derived from other sharding flavors; accepting one
            # would trade this loud rejection for a raw divisibility
            # crash deep inside jax
            raise ValueError(
                f"out_sharding must be a NamedSharding or a single "
                f"device, not {type(out_sharding).__name__}; wrap "
                "the consumer's layout in a NamedSharding over its "
                "mesh")
        if not out_sharding.is_fully_addressable:
            raise ValueError(
                "out_sharding places shards on devices this process "
                "cannot address; a multi-host scan gathers each "
                "host's results onto its LOCAL mesh — pass a "
                "per-process sharding (see MultiHostScan docs)")
        return out_sharding
    raise ValueError(
        f"out_sharding must be a NamedSharding, a PartitionSpec, or "
        f"a Sharding, not {type(out_sharding).__name__}")


def placement_devices(sharding) -> list:
    """The ordered device list of a resolved placement target — the
    order unit round-robin placement uses when decoding directly onto
    consumer shards (``read_row_groups_device(out_sharding=)``)."""
    if isinstance(sharding, NamedSharding):
        return list(sharding.mesh.devices.flat)
    return sorted(sharding.device_set, key=lambda d: d.id)


def dim0_partitions(sharding) -> int:
    """How many ways a resolved placement splits axis 0 (the unit
    axis of every gathered global).  The gather pads its unit axis to
    a multiple of this so the placed arrays satisfy jax's divisible-
    sharding requirement."""
    if isinstance(sharding, NamedSharding):
        spec = sharding.spec
        if len(spec) == 0 or spec[0] is None:
            return 1
        names = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
        t = 1
        shape = dict(sharding.mesh.shape)
        for nm in names:
            t *= shape[nm]
        return t
    return 1


class BatchedHybridPlan:
    """A stack of :class:`HybridPlan` padded to common static shapes.

    Array shapes (U = padded unit count, R = run bucket, B = bp-word
    bucket): ``bp_words`` (U, B_blocks*width) — flat per-unit rows, the
    unpack kernel reshapes inside its jit — ``run_ends`` /
    ``run_is_rle`` / ``run_value`` / ``run_bp_start`` (U, R).  ``count``
    is the padded per-unit value count; ``counts`` the true per-unit
    counts (for unpadding on the host afterwards).
    """

    __slots__ = ("bp_words", "run_ends", "run_is_rle", "run_value",
                 "run_bp_start", "count", "width", "n_bp", "counts",
                 "n_units")

    def __init__(self, bp_words, run_ends, run_is_rle, run_value,
                 run_bp_start, count, width, n_bp, counts, n_units):
        self.bp_words = bp_words
        self.run_ends = run_ends
        self.run_is_rle = run_is_rle
        self.run_value = run_value
        self.run_bp_start = run_bp_start
        self.count = count
        self.width = width
        self.n_bp = n_bp
        self.counts = counts
        self.n_units = n_units

    def arrays(self):
        return (self.bp_words, self.run_ends, self.run_is_rle,
                self.run_value, self.run_bp_start)


def stack_hybrid_plans(plans: list[HybridPlan], n_units: int | None = None,
                       count: int | None = None) -> BatchedHybridPlan:
    """Pad+stack host plans into one batch (see class docstring).

    Padding semantics: extra runs repeat the final ``run_end`` (so
    ``searchsorted(..., side="right")`` never selects them for real
    positions); extra units are all-RLE zero plans; positions past a
    unit's true count land in its final run and are masked off by the
    caller via ``counts``.
    """
    if not plans:
        raise ValueError("no plans to stack")
    width = max(p.width for p in plans)
    if any(p.width not in (width, 0) for p in plans):
        raise ValueError("mixed widths in one batch")
    true_n = len(plans)
    n_units = n_units or true_n
    R = bucket(max(len(p.run_ends) for p in plans))
    n_bp = bucket(max(p.n_bp_values for p in plans))
    count = count or bucket(max(p.count for p in plans))
    n_blocks = (n_bp + 31) // 32

    bp_words = np.zeros((n_units, n_blocks, max(width, 1)), dtype=np.uint32)
    run_ends = np.full((n_units, R), count, dtype=np.int32)
    run_is_rle = np.ones((n_units, R), dtype=bool)
    run_value = np.zeros((n_units, R), dtype=np.uint32)
    run_bp_start = np.zeros((n_units, R), dtype=np.int32)
    counts = np.zeros((n_units,), dtype=np.int32)

    for u, p in enumerate(plans):
        nb = p.bp_words.shape[0]
        bp_words[u, :nb, : p.bp_words.shape[1]] = p.bp_words
        nr = len(p.run_ends)
        run_ends[u, :nr] = p.run_ends
        run_ends[u, nr:] = max(int(p.run_ends[-1]), p.count) if nr else count
        run_is_rle[u, :nr] = p.run_is_rle
        run_value[u, :nr] = p.run_value
        run_bp_start[u, :nr] = p.run_bp_start
        counts[u] = p.count
    # per-unit bp words flatten to (U, B_blocks*width): a <=32 minor
    # dim would tile to 128 lanes on TPU; the unpack kernel reshapes
    # its 1-D row inside the jit
    return BatchedHybridPlan(bp_words.reshape(n_units, -1), run_ends,
                             run_is_rle, run_value, run_bp_start, count,
                             width, n_bp, counts, true_n)


def _expand_slice(bw, re, rr, rv, rs, idx, width: int, n_bp: int):
    """vmap body: one unit's plan, one slice of output positions."""
    return expand_hybrid_core(bw, re, rr, rv, rs, idx, width, n_bp)


def decode_step_spmd(mesh: Mesh, count: int, width: int, n_bp: int,
                     lanes: int):
    """Build the jitted SPMD decode step for one batch geometry.

    The step signature is ``step(bp_words, run_ends, run_is_rle,
    run_value, run_bp_start, dictionary) -> (U, count, lanes) u32`` with
    inputs sharded unit-wise over "rg" (dictionary replicated) and the
    output fully replicated (all-gathered over both axes) — the flagship
    "forward step" of the framework: hybrid-RLE/BP index expand +
    dictionary gather, data- and sequence-parallel.
    """
    sp = mesh.shape["sp"]
    if count % sp:
        raise ValueError(f"count={count} not divisible by sp={sp}")

    def step(bw, re, rr, rv, rs, dictionary):
        # Per-shard slice of the value axis (sequence parallel): shard i
        # of "sp" computes positions [i*count/sp, (i+1)*count/sp).
        i_sp = jax.lax.axis_index("sp")
        local = count // sp
        idx = i_sp * local + jnp.arange(local, dtype=jnp.int32)
        expand = jax.vmap(
            functools.partial(_expand_slice, width=width, n_bp=n_bp),
            in_axes=(0, 0, 0, 0, 0, None),
        )
        indices = expand(bw, re, rr, rv, rs, idx)          # (U_loc, local)
        vals = dictionary[jnp.minimum(indices, dictionary.shape[0] - 1)]
        # Reassemble the value axis, then gather units: both collectives
        # are XLA all-gathers over ICI (SURVEY.md §5 "distributed").
        vals = jax.lax.all_gather(vals, "sp", axis=1, tiled=True)
        return jax.lax.all_gather(vals, "rg", axis=0, tiled=True)

    spec_unit = P("rg")
    in_specs = (spec_unit, spec_unit, spec_unit, spec_unit, spec_unit, P())
    # check_vma=False: the output *is* replicated (all-gathered over
    # both axes) but the checker can't infer that through the gather.
    sharded = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                            out_specs=P(), check_vma=False)
    return jax.jit(sharded)


def sharded_dict_decode(mesh: Mesh, streams, counts, width: int,
                        dictionary: np.ndarray):
    """End-to-end sharded decode of many dict-index streams.

    ``streams``: list of raw hybrid-encoded index byte streams;
    ``counts``: per-stream value counts; ``dictionary``: (D, lanes) u32.
    Returns a list of (count_i, lanes) numpy arrays — the all-gathered,
    unpadded results, bit-identical on every host.
    """
    n_rg = mesh.shape["rg"]
    plans = [plan_hybrid(s, c, width) for s, c in zip(streams, counts)]
    n_units = max(len(plans), n_rg)
    n_units = ((n_units + n_rg - 1) // n_rg) * n_rg  # divisible by rg axis
    batch = stack_hybrid_plans(plans, n_units=n_units)
    count = batch.count
    sp = mesh.shape["sp"]
    if count % sp:
        count = int(math.ceil(count / sp) * sp)
        batch = stack_hybrid_plans(plans, n_units=n_units, count=count)
    step = decode_step_spmd(mesh, batch.count, batch.width, batch.n_bp,
                            dictionary.shape[1])
    unit_sharding = NamedSharding(mesh, P("rg"))
    rep = NamedSharding(mesh, P())
    args = [jax.device_put(a, unit_sharding) for a in batch.arrays()]
    dict_dev = jax.device_put(dictionary.astype(np.uint32), rep)
    out = np.asarray(step(*args, dict_dev))
    return [out[u, : batch.counts[u]] for u in range(batch.n_units)]
