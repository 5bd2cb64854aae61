"""Launcher CLI + elastic tests, driven through real subprocesses — the
reference's own pattern (test_parallel_dygraph_dataparallel.py:155 shells
out through the launcher; bash_test_modules in unittests/CMakeLists)."""

import os
import subprocess
import sys
import textwrap
import time

import pytest

import numpy as np

import paddle_tpu.distributed.launch as launch_mod
from paddle_tpu import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_launch(args, script_body, tmp_path, name="train.py"):
    script = tmp_path / name
    script.write_text(textwrap.dedent(script_body))
    env = dict(os.environ, PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         *args, str(script)],
        env=env, capture_output=True, text=True, timeout=120)


def test_launch_refuses_local_workers_that_could_share_chips(tmp_path):
    """One host is one process driving its chips: several local workers
    are refused unless their inherited environment pins JAX to the CPU
    (no worker is handed a chip of its own). In-process: the launcher
    parses and refuses before it spawns anything."""
    script = tmp_path / "train.py"
    script.write_text("raise SystemExit('worker must not start')")
    argv = ["--nproc_per_node", "2", str(script)]
    saved = os.environ.pop("JAX_PLATFORMS")
    try:
        with pytest.raises(SystemExit) as exc:
            launch_mod.launch(argv)
    finally:
        os.environ["JAX_PLATFORMS"] = saved
    assert "refused" in str(exc.value.code)
    assert "one process" in str(exc.value.code)


def test_launch_sets_env_contract(tmp_path):
    body = f"""
    import os
    rank = os.environ["PT_PROCESS_ID"]
    with open(r"{tmp_path}/rank_" + rank, "w") as f:
        f.write(":".join([os.environ["PT_NUM_PROCESSES"],
                          os.environ["PT_LOCAL_RANK"],
                          os.environ["PT_COORDINATOR"],
                          os.environ["PT_NNODES"]]))
    """
    r = _run_launch(["--nproc_per_node", "2", "--master", "127.0.0.1:7777"],
                    body, tmp_path)
    assert r.returncode == 0, r.stderr
    assert (tmp_path / "rank_0").read_text() == "2:0:127.0.0.1:7777:1"
    assert (tmp_path / "rank_1").read_text() == "2:1:127.0.0.1:7777:1"


def test_launch_node_rank_offsets_global_rank(tmp_path):
    body = f"""
    import os
    with open(r"{tmp_path}/g_" + os.environ["PT_LOCAL_RANK"], "w") as f:
        f.write(os.environ["PT_PROCESS_ID"])
    """
    r = _run_launch(["--nproc_per_node", "2", "--nnodes", "2",
                     "--node_rank", "1"], body, tmp_path)
    assert r.returncode == 0, r.stderr
    assert (tmp_path / "g_0").read_text() == "2"
    assert (tmp_path / "g_1").read_text() == "3"


def test_launch_propagates_failure_exit_code(tmp_path):
    body = """
    import os, sys
    sys.exit(3 if os.environ["PT_PROCESS_ID"] == "1" else 0)
    """
    r = _run_launch(["--nproc_per_node", "2"], body, tmp_path)
    assert r.returncode == 3, (r.returncode, r.stderr)


def test_launch_elastic_restart_recovers(tmp_path):
    body = f"""
    import os, sys
    marker = r"{tmp_path}/attempted"
    if not os.path.exists(marker):
        open(marker, "w").close()
        sys.exit(101)   # fail the first attempt
    open(r"{tmp_path}/ok_" + os.environ["PT_PROCESS_ID"], "w").close()
    """
    r = _run_launch(["--nproc_per_node", "2", "--max_restarts", "1"],
                    body, tmp_path)
    assert r.returncode == 0, r.stderr
    assert (tmp_path / "ok_0").exists() and (tmp_path / "ok_1").exists()
    assert "restart 1/1" in r.stderr


def test_launch_writes_worker_logs(tmp_path):
    body = """
    import os
    print("hello from rank", os.environ["PT_PROCESS_ID"], flush=True)
    """
    r = _run_launch(["--nproc_per_node", "2", "--log_dir",
                     str(tmp_path / "logs")], body, tmp_path)
    assert r.returncode == 0, r.stderr
    assert "rank 0" in (tmp_path / "logs" / "workerlog.0").read_text()
    assert "rank 1" in (tmp_path / "logs" / "workerlog.1").read_text()


@pytest.mark.skipif(not native.is_available(),
                    reason="native toolchain unavailable")
def test_elastic_manager_detects_dead_peer():
    from paddle_tpu.distributed.elastic import ElasticManager
    master = native.TCPStore(is_master=True)
    try:
        s0 = native.TCPStore(port=master.port)
        s1 = native.TCPStore(port=master.port)
        events = []
        m0 = ElasticManager(s0, rank=0, world_size=2, ttl=1.0,
                            interval=0.1,
                            on_change=lambda dead: events.append(dead))
        m1 = ElasticManager(s1, rank=1, world_size=2, ttl=1.0, interval=0.1)
        m0.start()
        m1.start()
        time.sleep(0.5)
        assert events == []  # both alive
        m1.stop()            # rank 1 "dies" (heartbeat stops)
        deadline = time.time() + 5
        while not events and time.time() < deadline:
            time.sleep(0.1)
        assert events and events[0] == [1]
        m0.stop()
        s0.close()
        s1.close()
    finally:
        master.close()


def test_check_nan_inf_sweep():
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.framework.debug import check_nan_inf, nan_inf_stats

    clean = {"a": jnp.ones((3,)), "b": (jnp.zeros((2,)), jnp.ones(()))}
    assert check_nan_inf(clean) is clean
    stats = nan_inf_stats({"x": jnp.asarray([1.0, np.nan, np.inf])})
    assert int(stats["x"]) == 2
    with pytest.raises(FloatingPointError, match="bad.*non-finite"):
        check_nan_inf({"bad": jnp.asarray([np.nan]), "ok": jnp.ones(2)})

    # hapi integration via the flag
    from paddle_tpu import nn, optimizer as optim
    from paddle_tpu.nn.module import Parameter

    class Blowup(nn.Module):
        def __init__(self):
            super().__init__()
            self.w = Parameter(jnp.asarray([[np.inf]], jnp.float32))

        def forward(self, x):
            return x @ self.w

    m = pt.Model(Blowup())
    m.prepare(optimizer=optim.SGD(learning_rate=1.0), loss=nn.MSELoss())
    pt.set_flags({"check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError):
            m.train_batch([np.ones((2, 1), np.float32)],
                          [np.ones((2, 1), np.float32)])
    finally:
        pt.set_flags({"check_nan_inf": False})


@pytest.mark.skipif(not native.is_available(),
                    reason="native toolchain unavailable")
def test_elastic_registry_reforms_rank_table():
    """Two 'node launchers' (threads) negotiate a rank table; round 2 has
    one fewer worker on node 1 → table re-forms at world 3 (≙ HTTPMaster /
    ETCDMaster membership, launch/controllers/master.py:66/:178)."""
    from paddle_tpu.distributed.elastic import ElasticRegistry
    import threading

    master_store = native.TCPStore(is_master=True)
    try:
        peer_store = native.TCPStore(port=master_store.port)
        master = ElasticRegistry(master_store, node_rank=0, is_master=True)
        peer = ElasticRegistry(peer_store, node_rank=1)

        results = {}

        def peer_round(version, n):
            peer.publish(version, n)
            results[version] = peer.wait_table(version, timeout=10.0)

        # round 1: 2 + 2 workers
        t = threading.Thread(target=peer_round, args=(1, 2))
        t.start()
        master.publish(1, 2)
        table, world = master.form_table(1, nnodes=2, grace=2.0)
        t.join()
        assert world == 4
        assert table == {0: (0, 2), 1: (2, 2)}
        assert results[1] == (table, 4)

        # round 2: node 1 lost a worker → world 3, contiguous ranks
        t = threading.Thread(target=peer_round, args=(2, 1))
        t.start()
        master.publish(2, 2)
        table2, world2 = master.form_table(2, nnodes=2, grace=2.0)
        t.join()
        assert world2 == 3
        assert table2 == {0: (0, 2), 1: (2, 1)}

        # round 3: node 1 gone entirely (never announces) → dropped after
        # the grace window
        master.publish(3, 2)
        table3, world3 = master.form_table(3, nnodes=2, grace=0.5)
        assert world3 == 2 and 1 not in table3
        peer_store.close()
    finally:
        master_store.close()


def test_checked_jit_catches_in_jit_nan_and_oob():
    """In-jit checkify (VERDICT 5.2: host sweep sees only outputs; this
    catches the producing primitive inside XLA, ≙ nan_inf_utils_detail)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.debug import checked_jit, check_in_jit

    def bad_log(x):
        return jnp.sum(jnp.log(x))  # NaN for negative input

    f = checked_jit(bad_log)
    assert np.isfinite(float(f(jnp.ones(3))))
    with pytest.raises(Exception, match="nan"):
        f(-jnp.ones(3))

    def oob(x, i):
        return x[i]

    g = checked_jit(oob)
    with pytest.raises(Exception, match="out-of-bounds|index"):
        g(jnp.arange(4.0), jnp.int32(9))

    def guarded(x):
        check_in_jit(jnp.all(x > 0), "x must be positive")
        return jnp.sqrt(x)

    from jax.experimental import checkify as _ck
    h = checked_jit(guarded, errors=_ck.user_checks)
    float(h(jnp.ones(2))[0])
    with pytest.raises(Exception, match="positive"):
        h(-jnp.ones(2))
    # under PLAIN jit the guard fails fast at trace time with a pointer
    # to the functionalizing wrapper, instead of silently dropping
    with pytest.raises(ValueError, match="checkify"):
        jax.jit(guarded)(-jnp.ones(2))
