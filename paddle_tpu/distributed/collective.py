"""Collective communication API.

Reference analog: ``paddle.distributed.{all_reduce, all_gather, …}`` backed
by ProcessGroupNCCL (paddle/fluid/distributed/collective/ProcessGroupNCCL.cc
— explicit comm streams, Task futures, c_sync_* ordering ops).

TPU-native: collectives are *program* constructs — jax.lax primitives over
named mesh axes inside jit/shard_map; XLA schedules them on ICI and the whole
stream-ordering layer (c_sync_calc_stream etc., SURVEY §5.8) has no
equivalent. These wrappers exist to (a) give reference users the same
vocabulary, (b) centralize axis-name defaults.

Inside shard_map-ed functions, `axis` accepts a mesh axis name or tuple.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ReduceOp", "all_reduce", "all_gather", "all_to_all",
           "reduce_scatter", "broadcast", "psum", "pmean", "pmax", "pmin",
           "ppermute", "axis_index", "axis_size", "send_recv_ring",
           "barrier", "Group", "new_group", "get_group", "group_reduce",
           "group_all_gather", "quantized_wire", "stripe_bytes"]


_wire_ctx = threading.local()


@contextlib.contextmanager
def quantized_wire(logical_bytes: int):
    """Byte-accounting scope for compressed wire formats (the EQuARX
    question: what actually crossed the link vs what the exchange is
    worth). Collectives issued inside record their REAL payload bytes
    (int8/fp8 blocks + scales) into ``comm/bytes_wire``, while
    ``comm/bytes_logical`` advances once by ``logical_bytes`` — the
    full-precision volume the same exchange would have moved. Outside any
    scope the wrappers tick both counters equally, so the two stay
    directly comparable and ``comm/compression_ratio`` (gauge,
    cumulative logical/wire) reads 1.0 for an uncompressed program.
    Like every ``_issue_span`` stat these tick at TRACE time — per
    compilation, not per step."""
    from paddle_tpu import stats
    prev = getattr(_wire_ctx, "active", False)
    _wire_ctx.active = True
    try:
        yield
    finally:
        _wire_ctx.active = prev
        # trace-time accounting BY DESIGN (see docstring): logical_bytes
        # is a static Python int computed from shapes, never a tracer
        # ptlint: disable=PT001,PT003 -- per-compilation counters, static arg
        stats.add("comm/bytes_logical", int(logical_bytes))
        wire = stats.get("comm/bytes_wire", 0)
        if wire:
            # ptlint: disable=PT003 -- per-compilation gauge, documented
            stats.set_value("comm/compression_ratio",
                            stats.get("comm/bytes_logical", 0) / wire)


def stripe_bytes(tier: str, nbytes: int):
    """FlexLink-style stripe accounting: wire bytes each stripe class of
    a striped collective moved (``comm/stripe_bytes_{ici,dcn}``) —
    per-compilation counters like every ``_issue_span`` stat, so the
    split a ``compression.quantized_bucket_reduce_scatter`` actually
    lowered is auditable against ``planner.stripe_plan``'s fraction."""
    from paddle_tpu import stats
    # ptlint: disable=PT003 -- per-compilation byte counters (see
    # quantized_wire: trace-time accounting is this module's contract)
    # ptlint: disable=PT001 -- nbytes is a static Python byte count
    stats.add(f"comm/stripe_bytes_{tier}", int(nbytes))


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _issue_span(name, x, axis):
    """Observability for the collective wrappers. These run at TRACE
    time (the op executes on-device inside the jitted program, where
    XLA owns the clock), so the span marks when the host issued the
    collective and tags its payload size — per-compilation, not
    per-step; on-device durations live in the XLA trace. Byte counters
    land in stats so an operator can attribute ICI traffic per op kind
    (the EQuARX-style question: which collective moves what)."""
    from paddle_tpu import stats
    from paddle_tpu.observability import trace
    try:
        nbytes = int(x.size) * int(jnp.dtype(x.dtype).itemsize)
    except Exception:
        nbytes = 0
    # ptlint: disable=PT003 -- trace-time recording is this helper's
    # documented contract (per-compilation, not per-step; see docstring)
    stats.add(f"collective/{name}_calls")
    if nbytes:
        # ptlint: disable=PT003 -- per-compilation byte counters
        stats.add(f"collective/{name}_bytes", nbytes)
        # ptlint: disable=PT003 -- per-compilation byte counters
        stats.add("comm/bytes_wire", nbytes)
        if not getattr(_wire_ctx, "active", False):
            # ptlint: disable=PT003 -- per-compilation byte counters
            stats.add("comm/bytes_logical", nbytes)
    if not trace.live():
        return contextlib.nullcontext()
    # ptlint: disable=PT003 -- issue-span semantics documented above
    return trace.span(f"collective/{name}", axis=str(axis),
                      bytes=nbytes)


def all_reduce(x, op=ReduceOp.SUM, axis="dp"):
    """ref: paddle.distributed.all_reduce → c_allreduce_{sum,max,min,prod}
    (operators/collective/c_allreduce_*). Must run inside shard_map/pjit."""
    with _issue_span("all_reduce", x, axis):
        if op == ReduceOp.SUM:
            return lax.psum(x, axis)
        if op == ReduceOp.MAX:
            return lax.pmax(x, axis)
        if op == ReduceOp.MIN:
            return lax.pmin(x, axis)
        if op == ReduceOp.AVG:
            return lax.pmean(x, axis)
        if op == ReduceOp.PROD:
            # gather-then-multiply: sign-correct for negatives/zeros (an
            # exp(psum(log)) trick would NaN on non-positive elements)
            return jnp.prod(lax.all_gather(x, axis), axis=0)
    raise ValueError(op)


psum = lax.psum
pmean = lax.pmean
pmax = lax.pmax
pmin = lax.pmin
ppermute = lax.ppermute


def all_gather(x, axis="dp", tiled_axis=0):
    """ref: c_allgather (operators/collective/c_allgather_op.cc)."""
    with _issue_span("all_gather", x, axis):
        return lax.all_gather(x, axis, axis=tiled_axis, tiled=True)


def reduce_scatter(x, axis="dp", scatter_axis=0):
    """ref: c_reducescatter."""
    with _issue_span("reduce_scatter", x, axis):
        return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis,
                                tiled=True)


def all_to_all(x, axis="ep", split_axis=0, concat_axis=0):
    """ref: alltoall op / global_scatter+global_gather MoE dispatch
    (operators/collective/global_scatter_op.cc)."""
    with _issue_span("all_to_all", x, axis):
        return lax.all_to_all(x, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def broadcast(x, src=0, axis="dp"):
    """ref: c_broadcast. Select src's shard and replicate."""
    with _issue_span("broadcast", x, axis):
        idx = lax.axis_index(axis)
        masked = jnp.where(idx == src, x, jnp.zeros_like(x))
        return lax.psum(masked, axis)


def axis_index(axis):
    return lax.axis_index(axis)


def axis_size(axis):
    return lax.axis_size(axis)


def send_recv_ring(x, axis="pp", shift=1):
    """Neighbor exchange on a ring (ref: send_v2/recv_v2 micro-batch P2P;
    on TPU a collective-permute rides ICI)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def barrier(axis=None):
    """ref: barrier op. Inside SPMD programs ordering is data-flow-driven;
    host-level barrier syncs all host processes. The span times the REAL
    host-side wait — a straggling rank shows up as one long barrier lane
    on every healthy rank's timeline."""
    if axis is None:
        import time as _time
        import jax.experimental.multihost_utils as mhu
        from paddle_tpu import stats
        from paddle_tpu.observability import trace
        with trace.span("collective/barrier"):
            t0 = _time.perf_counter()
            mhu.sync_global_devices("paddle_tpu_barrier")
            stats.observe("collective/barrier_s",
                          _time.perf_counter() - t0)


class Group:
    """Communicator subgroup (≙ paddle.distributed.collective.Group /
    new_group → ProcessGroup subsets). TPU-native: a subgroup is an
    ``axis_index_groups`` partition of a mesh axis — the XLA collective
    then runs independently inside each part, which is exactly what a
    sub-communicator does."""

    _next_id = 1

    def __init__(self, ranks, axis="dp", index_groups=None):
        self.ranks = list(ranks)
        self.axis = axis
        self.index_groups = index_groups
        self.id = Group._next_id
        Group._next_id += 1

    @property
    def nranks(self):
        return len(self.ranks)

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(id={self.id}, ranks={self.ranks}, axis={self.axis})"


_GROUPS = {}


def new_group(ranks=None, backend=None, axis="dp", world=None):
    """ref: paddle.distributed.new_group (collective.py:340). ``ranks``
    selects axis indices; the remaining indices are partitioned into
    equal-size groups when possible (the reference pattern — e.g. tp
    groups of 2 over 8 ranks) so every collective with
    ``axis_index_groups`` stays legal, else they form one complement
    group (psum-class reductions accept uneven parts)."""
    if world is None:
        from paddle_tpu.distributed.mesh import get_mesh
        m = get_mesh()
        world = dict(m.shape)[axis] if m is not None else len(jax.devices())
    all_idx = list(range(world))
    ranks = all_idx if ranks is None else sorted(int(r) for r in ranks)
    if len(set(ranks)) != len(ranks):
        raise ValueError(f"new_group: duplicate ranks {ranks}")
    bad = [r for r in ranks if not 0 <= r < world]
    if bad:
        raise ValueError(f"new_group: ranks {bad} out of range "
                         f"[0, {world})")
    rest = [i for i in all_idx if i not in ranks]
    groups = [ranks]
    if rest:
        n = len(ranks)
        if len(rest) % n == 0:
            groups += [rest[i:i + n] for i in range(0, len(rest), n)]
        else:
            groups.append(rest)
    g = Group(ranks, axis=axis, index_groups=groups)
    _GROUPS[g.id] = g
    return g


def get_group(gid):
    return _GROUPS.get(gid)


def _part_table(group):
    """part-id per axis index, as a static lookup array."""
    import numpy as np
    world = sum(len(p) for p in group.index_groups)
    table = np.zeros(world, np.int32)
    for pid, part in enumerate(group.index_groups):
        for r in part:
            table[r] = pid
    return jnp.asarray(table)


def group_reduce(x, op=ReduceOp.SUM, group: "Group" = None):
    """Collective restricted to ``group`` (inside shard_map): ranks in
    the group reduce among themselves; other ranks reduce within their
    own partition part. Mechanism: full-axis all_gather + a static
    part-membership mask (shard_map does not lower axis_index_groups),
    then a masked reduction — one gather instead of a sub-communicator,
    which on a TPU mesh is the same ICI traffic class."""
    if group is None:
        return all_reduce(x, op=op)
    table = _part_table(group)
    my_part = table[lax.axis_index(group.axis)]
    gathered = lax.all_gather(x, group.axis)  # (world, ...)
    mask = (table == my_part)
    mshape = (-1,) + (1,) * (gathered.ndim - 1)
    m = mask.reshape(mshape)
    dt = gathered.dtype
    # dtype-preserving identities (an inf mask would promote ints to f32)
    if jnp.issubdtype(dt, jnp.integer):
        lo, hi = jnp.iinfo(dt).min, jnp.iinfo(dt).max
    else:
        lo, hi = -jnp.inf, jnp.inf
    if op == ReduceOp.SUM:
        return jnp.sum(jnp.where(m, gathered, 0), axis=0)
    if op == ReduceOp.AVG:
        return (jnp.sum(jnp.where(m, gathered, 0), axis=0)
                / jnp.sum(mask))
    if op == ReduceOp.MAX:
        return jnp.max(jnp.where(m, gathered, lo), axis=0)
    if op == ReduceOp.MIN:
        return jnp.min(jnp.where(m, gathered, hi), axis=0)
    if op == ReduceOp.PROD:
        return jnp.prod(jnp.where(m, gathered, jnp.ones((), dt)), axis=0)
    raise ValueError(f"group_reduce: unsupported op {op}")


def group_all_gather(x, group: "Group", tiled_axis=0):
    """all_gather inside ``group`` — parts must be equal-size (so every
    rank's result has one static shape)."""
    sizes = {len(p) for p in group.index_groups}
    if len(sizes) != 1:
        raise ValueError("group_all_gather needs equal-size parts; "
                         f"got {group.index_groups}")
    import numpy as np
    table = _part_table(group)
    my_part = table[lax.axis_index(group.axis)]
    members = jnp.asarray(np.asarray(group.index_groups, np.int32))
    gathered = lax.all_gather(x, group.axis)       # (world, ...)
    rows = gathered[members[my_part]]              # (part_size, ...)
    part = rows.shape[0]
    # concatenate the per-member shards along tiled_axis (same contract
    # as all_gather(..., tiled=True))
    return jnp.concatenate([rows[i] for i in range(part)],
                           axis=tiled_axis)


# -- reference-name parity over the same lax machinery ----------------------

def alltoall(in_tensor_list, out_tensor_list=None, axis="ep"):
    """ref: paddle.distributed.alltoall (list-of-tensors form): rank r's
    i-th input lands as the r-th output of rank i. In-program form: stack
    → all_to_all → unstack."""
    x = jnp.stack([jnp.asarray(t) for t in in_tensor_list])
    out = lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=False)
    outs = [out[i] for i in range(out.shape[0])]
    if out_tensor_list is not None:
        del out_tensor_list[:]
        out_tensor_list.extend(outs)
    return outs


def alltoall_single(x, axis="ep", split_axis=0, concat_axis=0):
    """ref: paddle.distributed.alltoall_single (even splits)."""
    return lax.all_to_all(x, axis, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def reduce(x, dst=0, op=ReduceOp.SUM, axis="dp"):
    """ref: paddle.distributed.reduce — the reduced value lands on rank
    ``dst``; other ranks keep their input (the reference leaves their
    output buffer unspecified; keeping the input is deterministic)."""
    red = all_reduce(x, op=op, axis=axis)
    return jnp.where(lax.axis_index(axis) == dst, red, x)


def scatter(x, src=0, axis="dp"):
    """ref: paddle.distributed.scatter — rank ``src``'s input, split into
    axis-size chunks along dim 0; rank i receives chunk i."""
    full = broadcast(x, src=src, axis=axis)
    n = lax.axis_size(axis)
    if full.shape[0] % n:
        raise ValueError(f"scatter: dim 0 ({full.shape[0]}) must divide "
                         f"evenly over axis {axis!r} ({n} ranks)")
    chunk = full.shape[0] // n
    i = lax.axis_index(axis)
    return lax.dynamic_slice_in_dim(full, i * chunk, chunk, axis=0)


def split(x, weight=None, bias=None, operation="linear", axis=1,
          num_partitions=None, gather_out=True):
    """ref: paddle.distributed.split (fleet/layers/mpu) — the
    megatron-style model-parallel linear/embedding splitter; delegates to
    distributed/mp_ops.py's column/row helpers. ``axis``: 1 = column
    (output-dim) parallel, 0 = row (input-dim) parallel."""
    if operation == "embedding":
        # in-shard_map vocab-parallel lookup (split's linear branch also
        # assumes the caller's shard_map): each rank holds a contiguous
        # row range of the table; out-of-range ids read row 0 masked to
        # zero, psum over tp sums exactly one live contribution
        ids = jnp.asarray(x)
        per = weight.shape[0]
        start = lax.axis_index("tp") * per
        local = ids - start
        in_range = (local >= 0) & (local < per)
        rows = weight[jnp.clip(local, 0, per - 1)]
        rows = jnp.where(in_range[..., None], rows, 0.0)
        return lax.psum(rows, "tp")
    if operation != "linear":
        raise ValueError(f"split: unknown operation {operation!r}")
    if axis == 1:
        out = jnp.asarray(x) @ weight  # weight already column-sharded
        if bias is not None:
            out = out + bias
        if gather_out:
            out = lax.all_gather(out, "tp", axis=out.ndim - 1, tiled=True)
        return out
    out = jnp.asarray(x) @ weight      # row-parallel: partial sums
    out = lax.psum(out, "tp")
    if bias is not None:
        out = out + bias
    return out


class ParallelMode:
    """ref: paddle.distributed.ParallelMode enum."""
    DATA_PARALLEL = 0
    TENSOR_PARALLEL = 1
    PIPELINE_PARALLEL = 2
    SHARDING_PARALLEL = 3


class _StreamNamespace:
    """ref: paddle.distributed.stream.* — the stream-annotated collective
    variants. XLA owns stream scheduling, so each maps to the plain op."""

    def __getattr__(self, name):
        import sys
        mod = sys.modules[__name__]
        if hasattr(mod, name):
            return getattr(mod, name)
        raise AttributeError(f"stream has no collective {name!r}")


stream = _StreamNamespace()

__all__ += ["alltoall", "alltoall_single", "reduce", "scatter", "split",
            "ParallelMode", "stream"]
