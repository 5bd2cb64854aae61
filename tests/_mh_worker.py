"""Spawned-worker module for test_multihost. Pins the CPU platform at
MODULE level: multiprocessing's spawn start-method unpickles the target
function by importing this module, so these lines run before any jax
backend can initialize (a chip belongs to one process: two workers
must never both reach for it)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402


def _set_cpu_device_count(n):
    """Per-process CPU device count, pre-backend-init. jax >= 0.5 has a
    config option; older jax only honors the XLA flag (these lines run
    before any backend initializes, so mutating XLA_FLAGS still takes)."""
    import jax
    try:
        jax.config.update("jax_num_cpu_devices", n)
    except AttributeError:
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={n}")
        os.environ["XLA_FLAGS"] = " ".join(flags)


def worker(tmpdir):
    import jax
    jax.config.update("jax_platforms", "cpu")
    # federate the per-process CPU devices into one global backend
    # (cross-process CPU collectives run over gloo; on TPU pods the ICI/
    # DCN fabric takes this role and no flag is needed). One device per
    # process — conftest's xla_force_host_platform_device_count=8 leaks
    # into spawned children through the environment.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    _set_cpu_device_count(1)
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.experimental.shard_map import shard_map

    import paddle_tpu.distributed as dist

    dist.init_parallel_env()      # PT_* env → jax.distributed.initialize
    rank = dist.get_rank()
    world = jax.process_count()
    assert world == 2, world
    devices = jax.devices()       # global view: one device per process
    assert len(devices) == 2

    mesh = Mesh(np.array(devices), ("dp",))

    # cross-process psum through shard_map (the NCCL-allreduce analog on
    # the DCN plane)
    @jax.jit
    def allreduce(x):
        return shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                         in_specs=P("dp"), out_specs=P("dp"))(x)

    local = jnp.full((1, 4), float(rank + 1))
    glob = jax.make_array_from_single_device_arrays(
        (2, 4), NamedSharding(mesh, P("dp")),
        [jax.device_put(local, devices[rank])])
    out = allreduce(glob)
    got = np.asarray(out.addressable_shards[0].data)
    np.testing.assert_allclose(got, np.full((1, 4), 3.0))  # 1 + 2

    # cross-process pipeline tick: roll(+1) as collective-permute BETWEEN
    # THE TWO PROCESSES — the PP-over-DCN mechanism (≙ FleetExecutor's
    # cross-rank interceptor sends)
    @jax.jit
    def ring_shift(x):
        return shard_map(
            lambda v: jax.lax.ppermute(
                v, "dp", perm=[(i, (i + 1) % 2) for i in range(2)]),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))(x)

    shifted = ring_shift(glob)
    got = np.asarray(shifted.addressable_shards[0].data)
    expect = np.full((1, 4), float(((rank - 1) % 2) + 1))
    np.testing.assert_allclose(got, expect)

    with open(os.path.join(tmpdir, f"ok_{rank}"), "w") as f:
        f.write("1")


def obs_worker(tmpdir):
    """Per-rank tracing body for the trace-merge test: each spawned
    process (PT_PROCESS_ID set by dist.spawn's env contract) records a
    nested span tree and exports its own trace_rank{N}.json — the
    parent test merges them and asserts distinct rank lanes. No jax
    needed: the tracer is pure host-side."""
    from paddle_tpu import stats
    from paddle_tpu.observability import span, trace

    rank = int(os.environ["PT_PROCESS_ID"])
    trace.enable(os.path.join(tmpdir, f"trace_rank{rank}.json"),
                 capacity=256)
    with span("mh/work", rank=rank):
        with span("mh/inner"):
            stats.observe("mh/latency_s", 0.001 * (rank + 1))
    path = trace.export()
    # worker-side stats export rides a sidecar file, the way launch-side
    # aggregation would scrape statsz: the parent merges both ranks
    import json
    with open(os.path.join(tmpdir, f"stats_{rank}.json"), "w") as f:
        json.dump(stats.export(rank=rank), f)
    assert path is not None


# ---------------------------------------------------------------------------
# Two-controller GPT hybrid step (VERDICT r4 item 4): 2 processes x 4
# virtual CPU devices = one 8-device jax.distributed job running the FULL
# dp x fsdp x tp GPT train step; losses must match the single-controller
# 8-device run bit-for-tolerance. Ref: test_dist_base.py:901 (subprocess
# hybrid suites), test_collective_api_base.py:292.
# ---------------------------------------------------------------------------

GPT_MESH = {"dp": 2, "fsdp": 2, "tp": 2}
GPT_STEPS = 3


def _gpt_mini():
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=512, max_seq_len=16, d_model=32,
                        n_layers=2, n_heads=2, dtype=jnp.float32)
    return gpt.GPT(cfg, seed=0)


def _gpt_tokens():
    return np.random.RandomState(0).randint(0, 512, (8, 16)).astype(
        np.int32)


def gpt_losses(mesh_degrees=GPT_MESH, steps=GPT_STEPS):
    """Run the hybrid GPT step on the CURRENT backend's 8 devices; works
    single-controller (pytest process) and multi-controller (each process
    passes identical replicated inputs, jit computes the same global
    program). Returns the loss sequence."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu.models import gpt
    from paddle_tpu import optimizer as optim

    topo = dist.init_mesh(**mesh_degrees)
    model = _gpt_mini()
    opt = optim.AdamW(learning_rate=1e-3)
    params, _ = model.split_params()
    # multi-controller-safe placement: device_put cannot target
    # non-addressable devices, but a jitted identity with out_shardings
    # can produce globally-sharded outputs on every controller
    shardings = gpt.param_shardings(params, topo.mesh)
    params = jax.jit(lambda p: p, out_shardings=shardings)(params)
    opt_state = jax.jit(opt.init)(params)
    step = gpt.build_train_step(model, opt)
    tokens = jnp.asarray(_gpt_tokens())
    rng = jax.random.PRNGKey(0)
    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, tokens, rng)
        losses.append(float(loss))  # fully-replicated scalar
    return losses


def gpt_worker(tmpdir):
    import json
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    _set_cpu_device_count(4)

    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    rank = dist.get_rank()
    assert jax.process_count() == 2
    assert jax.device_count() == 8 and len(jax.local_devices()) == 4

    losses = gpt_losses()
    with open(os.path.join(tmpdir, f"losses_{rank}.json"), "w") as f:
        json.dump(losses, f)


# ---------------------------------------------------------------------------
# FleetExecutor pipeline split across the two controllers: each process
# owns ONE stage as its own jitted program over its LOCAL 4-device mesh
# (in-stage dp x tp SPMD), boundary activations cross controllers over the
# native P2P endpoint — DCN-PP composed with ICI-SPMD, the way a real
# 2-host pod splits NCCL (intra) from brpc (inter) in the reference.
# ---------------------------------------------------------------------------

FE_D, FE_H, FE_MICRO, FE_B = 8, 16, 4, 4


def _fe_data():
    rs = np.random.RandomState(7)
    x = rs.normal(size=(FE_MICRO, FE_B, FE_D)).astype(np.float32)
    y = rs.normal(size=(FE_MICRO, FE_B, FE_D)).astype(np.float32)
    return x, y


def _fe_params(stage):
    rs = np.random.RandomState(10 + stage)
    din, dout = (FE_D, FE_H) if stage == 0 else (FE_H, FE_D)
    return {"w": rs.normal(size=(din, dout)).astype(np.float32) * 0.3}


def fe_reference():
    """Single-process full-model oracle for the 2-stage MLP."""
    import jax
    import jax.numpy as jnp
    x, y = _fe_data()
    ps = [_fe_params(0), _fe_params(1)]

    def loss_fn(ps):
        total = 0.0
        for mb in range(FE_MICRO):
            h = jnp.maximum(x[mb] @ ps[0]["w"], 0.0)
            pred = h @ ps[1]["w"]
            total = total + jnp.mean(jnp.square(pred - y[mb]))
        return total / FE_MICRO

    return float(loss_fn(ps)), jax.grad(loss_fn)(ps)


def fe_worker(tmpdir, store_port):
    import json
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    _set_cpu_device_count(4)
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu.distributed as dist
    from paddle_tpu import native
    from paddle_tpu.distributed.fleet_executor import (
        FleetExecutor, rendezvous_endpoints)

    dist.init_parallel_env()
    rank = dist.get_rank()
    # in-stage SPMD over THIS controller's local devices only
    local = Mesh(np.array(jax.local_devices()).reshape(2, 2),
                 ("dp", "tp"))

    def constrain(h):
        return jax.lax.with_sharding_constraint(
            h, NamedSharding(local, P("dp", "tp")))

    if rank == 0:
        def stage(params, x):
            return jnp.maximum(constrain(x @ params["w"]), 0.0)
    else:
        def stage(params, x, label):
            pred = constrain(x @ params["w"])
            return jnp.mean(jnp.square(pred - label))

    store = native.TCPStore("127.0.0.1", store_port,
                            is_master=(rank == 0), timeout=60.0)
    ep, peers = rendezvous_endpoints(store, rank, 2)
    fe = FleetExecutor(stage, rank, 2, ep, peers, schedule="1f1b")
    try:
        x, y = _fe_data()
        params = _fe_params(rank)
        grads, loss = fe.run(
            params,
            microbatches=list(x) if rank == 0 else None,
            labels=list(y) if rank == 1 else None,
            n_micro=FE_MICRO)
        rec = {"grad_w_sum": float(np.asarray(grads["w"]).sum())}
        if loss is not None:
            rec["loss"] = float(loss)
        with open(os.path.join(tmpdir, f"fe_{rank}.json"), "w") as f:
            json.dump(rec, f)
    finally:
        fe.close()
