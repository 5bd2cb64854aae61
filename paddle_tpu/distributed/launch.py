"""Multi-process / multi-host job launcher CLI.

Reference analog: ``python -m paddle.distributed.launch``
(launch/main.py:18 → CollectiveController controllers/collective.py:21):
build the env contract per rank, spawn workers, tail logs, watch children,
relaunch on failure (elastic, ≙ CollectiveElasticController :184 /
ElasticManager fleet/elastic/manager.py:128 — etcd replaced by the native
TCPStore).

Usage:
    python -m paddle_tpu.distributed.launch \
        --nproc_per_node 1 --nnodes 2 --node_rank 0 \
        --master 10.0.0.1:8765 train.py --lr 1e-4

Env contract written for each worker (read by env.init_parallel_env):
    PT_COORDINATOR     jax.distributed coordinator "host:port"
    PT_NUM_PROCESSES   total worker processes across nodes
    PT_PROCESS_ID      global rank of this worker
    PT_LOCAL_RANK      rank within this node
    PT_NNODES          node count

Observability contract (docs/observability.md): with PT_TRACE_DIR set
on the launcher, each worker gets PT_TRACE_FILE =
``$PT_TRACE_DIR/trace_rank{rank}.json`` and on exit the launcher merges
every rank file into ``trace_merged.json`` — one Perfetto timeline with
a lane per rank. With PT_STATSZ_PORT set, worker rank r serves its live
statsz on ``base + 1 + r`` (the launcher itself holds ``base``), so a
node's whole worker group is scrapeable from adjacent ports.
"""

import argparse
import os
import signal
import subprocess
import sys
import threading
import time

__all__ = ["launch", "main"]

ELASTIC_EXIT_CODE = 101  # ≙ fleet/elastic/manager.py:32


def _obs_env(rank):
    """Per-rank observability env (module docstring: per-rank trace
    file; statsz at base + 1 + rank so worker 0 never collides with the
    launcher's own server on base)."""
    out = {}
    tdir = os.environ.get("PT_TRACE_DIR")
    if tdir:
        out["PT_TRACE_FILE"] = os.path.join(
            tdir, f"trace_rank{rank}.json")
    base = os.environ.get("PT_STATSZ_PORT")
    if base:
        try:
            out["PT_STATSZ_PORT"] = str(int(base) + 1 + rank)
        except ValueError:
            pass
    return out


def _merge_traces_on_exit():
    """Fold every rank's trace file in PT_TRACE_DIR into ONE Perfetto
    timeline (trace_merged.json, rank → pid lane). Runs after the
    worker group exits; a worker that died before exporting simply
    contributes no lane — merging must never mask the job's own exit
    code, so failures only warn."""
    tdir = os.environ.get("PT_TRACE_DIR")
    if not tdir:
        return
    try:
        from paddle_tpu.observability import merge
        out = merge.merge_rank_traces(tdir)
        if out:
            print(f"[launch] merged rank traces -> {out}",
                  file=sys.stderr)
        # serving traces carry per-request trace contexts (args.rid):
        # also emit the stitched per-request timeline. A training job's
        # traces have no rids — stitch_rank_traces then writes nothing
        stitched = merge.stitch_rank_traces(tdir)
        if stitched:
            print(f"[launch] stitched request timeline -> {stitched}",
                  file=sys.stderr)
    except Exception as e:
        print(f"[launch] trace merge failed: {e}", file=sys.stderr)


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch a (multi-host) training job")
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--nnodes", default="1",
                   help="node count, or MIN:MAX for an elastic range "
                        "(≙ the reference's --np 2:4): the job runs with "
                        "whatever node count inside the range announces "
                        "each membership round, so late nodes can JOIN")
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--master", default="127.0.0.1:8765",
                   help="host:port of the jax.distributed coordinator "
                        "(process 0)")
    p.add_argument("--log_dir", default=None,
                   help="write per-rank workerlog.N files here")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="relaunch the local group this many times on "
                        "worker failure (elastic)")
    p.add_argument("--elastic", action="store_true",
                   help="membership-changing mode: on worker failure the "
                        "job RE-FORMS at the surviving world size (ranks "
                        "reassigned via the TCPStore registry) instead of "
                        "restarting at the same size")
    p.add_argument("--elastic_grace", type=float, default=1.0,
                   help="seconds the master waits for straggler nodes "
                        "when forming a membership round")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    lo, _, hi = str(args.nnodes).partition(":")
    try:
        args.nnodes_min = int(lo)
        args.nnodes_max = int(hi) if hi else args.nnodes_min
    except ValueError:
        p.error(f"--nnodes must be N or MIN:MAX, got {args.nnodes!r}")
    if not 1 <= args.nnodes_min <= args.nnodes_max:
        p.error(f"--nnodes range must satisfy 1 <= MIN <= MAX, "
                f"got {args.nnodes!r}")
    args.nnodes = args.nnodes_max
    return args


def _spawn(args, local_rank, rank=None, world=None, extra_env=None):
    if world is None:
        world = args.nnodes * args.nproc_per_node
    if rank is None:
        rank = args.node_rank * args.nproc_per_node + local_rank
    env = dict(os.environ)
    env.update({
        "PT_COORDINATOR": args.master,
        "PT_NUM_PROCESSES": str(world),
        "PT_PROCESS_ID": str(rank),
        "PT_LOCAL_RANK": str(local_rank),
        "PT_NNODES": str(args.nnodes),
    })
    env.update(_obs_env(rank))
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, args.training_script,
           *args.training_script_args]
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
        logf = open(os.path.join(args.log_dir, f"workerlog.{rank}"), "ab")
        stdout = stderr = logf
    elif local_rank == 0:
        logf = None
        stdout = stderr = None  # inherit: rank 0 streams to console
    else:
        logf = open(os.devnull, "wb")
        stdout = stderr = logf
    proc = subprocess.Popen(cmd, env=env, stdout=stdout, stderr=stderr,
                            start_new_session=True)
    proc._pt_logf = logf
    proc._pt_rank = rank
    return proc


def _kill_group(procs):
    for p in procs:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGTERM)
                # mark launcher-inflicted SIGTERMs the same way as the
                # SIGKILL escalation below: the reshape survivor count
                # must distinguish a healthy group-kill casualty from a
                # worker an EXTERNAL supervisor signaled (preemption)
                p._pt_launcher_terminated = True
            except ProcessLookupError:
                pass
    deadline = time.time() + 5
    for p in procs:
        try:
            p.wait(timeout=max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            try:
                os.killpg(p.pid, signal.SIGKILL)
                # mark the escalation: a LAUNCHER-inflicted SIGKILL (a
                # healthy worker blocked past the SIGTERM grace, e.g.
                # mid-collective) must not read as an external
                # preemption to the reshape survivor count
                p._pt_launcher_killed = True
            except ProcessLookupError:
                pass
            # reap the escalated child: without this its returncode
            # stays None and the marker above is never consulted by
            # the reshape classification (and the child stays a
            # zombie until the Popen is collected)
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass
    for p in procs:
        if p._pt_logf:
            p._pt_logf.close()


def _watch(procs, poll_s=0.2, should_abort=None, coalesce_s=0.0):
    """Block until all exit 0 (return 0) or any fails (kill rest, return
    its code). ≙ ControllerBase.watch (launch/controllers/controller.py:34).
    ``should_abort()`` (elastic): polled each tick; truthy → kill the
    group and return REFORM_RC (another node asked for a re-form).
    ``coalesce_s`` (reshape accounting): a preemption reclaims several
    workers near-simultaneously, so after the first failure wait this
    long for the co-failures to land before killing the group —
    otherwise the group SIGTERM races a sibling's own exit and the
    survivor count reads one casualty as healthy."""
    while True:
        alive = False
        rc_fail = None
        for p in procs:
            rc = p.poll()
            if rc is None:
                alive = True
            elif rc != 0 and rc_fail is None:
                rc_fail = rc
        if rc_fail is not None:
            if coalesce_s > 0 and alive:
                deadline = time.monotonic() + coalesce_s
                while (time.monotonic() < deadline
                       and any(p.poll() is None for p in procs)):
                    time.sleep(0.05)
            _kill_group(procs)
            return rc_fail
        if not alive:
            return 0
        if should_abort is not None and should_abort():
            _kill_group(procs)
            return REFORM_RC
        time.sleep(poll_s)


REFORM_RC = -1000  # internal: group killed because membership changed


def _launch_elastic(args):
    """Membership-changing controller (≙ CollectiveElasticController,
    launch/controllers/collective.py:184, with the etcd master replaced by
    ElasticRegistry on the native TCPStore).

    Round protocol: the master announces round v on ``elastic/round``;
    every node publishes its alive worker count for v; the master forms
    the rank table; every node (re)launches its local group with the
    assigned global ranks and the NEW world size. A worker failure on any
    node bumps ``elastic/reform``, which aborts every group and starts
    round v+1 with the failed workers removed — N→N−1 re-formation, not
    same-size restart (VERDICT r2 item 5)."""
    from paddle_tpu import native
    from paddle_tpu.distributed.elastic import ElasticRegistry

    host, port = args.master.rsplit(":", 1)
    reg_port = int(port) + 1
    is_master = args.node_rank == 0
    store = (native.TCPStore("127.0.0.1", reg_port, is_master=True)
             if is_master else native.TCPStore(host, reg_port))
    reg = ElasticRegistry(store, args.node_rank, is_master=is_master)
    n_local = args.nproc_per_node
    version = 0
    attempt = 0
    reform_seen = 0
    # pure reshape requests (every local worker exited ELASTIC_EXIT_CODE)
    # don't burn the restart budget, so a deterministically recurring
    # re-form (e.g. a peer that wedges the same way every generation)
    # needs its own bound or the launcher loops forever
    pure_reforms = 0
    join_attempts = 0
    try:
        while True:
            version += 1
            if is_master:
                store.set("elastic/round", str(version))
            else:
                while True:
                    v = int(store.get("elastic/round", timeout=60.0))
                    if v >= version:
                        version = v
                        break
                    time.sleep(0.1)
            reg.publish(version, n_local)
            try:
                if is_master:
                    reg.form_table(version, args.nnodes,
                                   grace=args.elastic_grace,
                                   nnodes_min=args.nnodes_min)
                table, world = reg.wait_table(version)
            except TimeoutError as e:
                # below-minimum membership (a node is late) is a WAIT
                # state, not a crash: announce the next round and keep
                # trying — the elastic semantics (≙ manager.py's watch
                # loop idling until min nodes register)
                print(f"[launch] round {version} incomplete ({e}); "
                      f"retrying", file=sys.stderr)
                time.sleep(1.0)
                continue
            if args.node_rank in table:
                join_attempts = 0  # an established member re-earns its
                # join budget for any later re-form race
            if args.node_rank not in table:
                if not is_master and n_local > 0:
                    # late JOINER (≙ manager.py:128 node-join watch): the
                    # round's table was formed before this node announced;
                    # ask the cluster to re-form and try the next round
                    join_attempts += 1
                    if join_attempts <= 3:
                        from paddle_tpu import stats
                        stats.add("launch/join_requests")
                        print(f"[launch] node {args.node_rank} joining: "
                              f"requesting re-form after round {version}",
                              file=sys.stderr)
                        reform_seen = store.add("elastic/reform", 1)
                        time.sleep(0.5)
                        continue
                    # the node never made it into a table: exiting 0 would
                    # read as success to the operator's orchestration
                    print(f"[launch] node {args.node_rank} failed to join "
                          f"after {join_attempts - 1} re-form requests",
                          file=sys.stderr)
                    return 1
                if not is_master:
                    store.set(f"elastic/done/{version}/{args.node_rank}", "1")
                    return 0  # dropped from membership; nothing to run
                # the master hosts the registry server: even with zero
                # local workers it must coordinate until the surviving
                # nodes finish (or drive the next re-form round)
                if not table:
                    return 1
                status, reform_seen = _master_wait_members(
                    store, table, version, reform_seen)
                if status == "reform":
                    continue
                return 0
            start, n = table[args.node_rank]
            from paddle_tpu import stats
            stats.add("launch/rounds")       # round 1 = form, 2+ = re-forms
            stats.add("launch/reforms", 1 if stats.get("launch/rounds") > 1
                      else 0)
            stats.set_value("launch/world_size", world)
            print(f"[launch] elastic round {version}: world={world} "
                  f"local={n} start_rank={start}", file=sys.stderr)
            procs = [_spawn(args, i, rank=start + i, world=world,
                            extra_env={"PT_ELASTIC_VERSION": str(version),
                                       "PT_RESTART_ATTEMPT": str(attempt)})
                     for i in range(n)]

            def reform_requested():
                nonlocal reform_seen
                try:
                    c = native.decode_counter(
                        store.get("elastic/reform", timeout=0.2))
                except (TimeoutError, ValueError):
                    return False
                if c > reform_seen:
                    reform_seen = c
                    return True
                return False

            rc = _watch(procs, should_abort=reform_requested)
            if rc == 0:
                store.set(f"elastic/done/{version}/{args.node_rank}", "1")
                if is_master:
                    # keep the registry alive for surviving members; if
                    # one of them asks for a re-form, keep coordinating
                    # with zero local workers
                    status, reform_seen = _master_wait_members(
                        store, table, version, reform_seen)
                    if status == "reform":
                        n_local = 0
                        continue
                return 0
            if rc != REFORM_RC:
                # local failure: shrink membership and ask the cluster to
                # re-form. Only LOCAL failures consume the restart budget;
                # a healthy node aborted by a peer's re-form request must
                # not burn its own budget (it did nothing wrong). A worker
                # exiting ELASTIC_EXIT_CODE is a SURVIVOR asking for a
                # re-form (ElasticManager saw a remote peer die) — it is
                # not a local failure and must not shrink the local count.
                n_failed = sum(1 for p in procs
                               if (p.returncode or 0) > 0
                               and p.returncode != ELASTIC_EXIT_CODE)
                n_reshape = sum(1 for p in procs
                                if p.returncode == ELASTIC_EXIT_CODE)
                if n_failed == 0 and n_reshape > 0:
                    pure_reforms += 1
                    if pure_reforms > max(8, 4 * args.max_restarts):
                        return rc
                    reform_seen = store.add("elastic/reform", 1)
                else:
                    attempt += 1
                    n_local = n - max(1, n_failed)
                    reform_seen = store.add("elastic/reform", 1)
                    if n_local <= 0 and args.nnodes == 1:
                        return rc
                    if attempt > args.max_restarts:
                        return rc
            print(f"[launch] re-forming after rc={rc}; attempt "
                  f"{attempt}/{args.max_restarts}", file=sys.stderr)
    finally:
        store.close()


def _master_wait_members(store, table, version, reform_seen,
                         timeout=600.0):
    """The master's launcher hosts the registry server in-process: it must
    outlive every member node's round, or survivors lose their control
    plane mid-job. Blocks until each member posts its done key — or a
    member requests a re-form (returns ("reform", counter) so the master
    loop can drive the next round even with zero local workers)."""
    from paddle_tpu import native

    deadline = time.time() + timeout
    pending = set(table)
    while pending and time.time() < deadline:
        for node in list(pending):
            try:
                store.get(f"elastic/done/{version}/{node}", timeout=0.2)
                pending.discard(node)
            except TimeoutError:
                pass
        try:
            c = native.decode_counter(
                        store.get("elastic/reform", timeout=0.2))
            if c > reform_seen:
                return ("reform", c)
        except (TimeoutError, ValueError):
            pass
    return ("done", reform_seen)


def _refuse_shared_chips(args):
    """One host is ONE process driving its chips: a chip belongs to one
    process at a time, and nothing here hands worker i a chip of its
    own. Several local workers are therefore CPU workers by contract —
    unless the environment they inherit pins JAX to the CPU, every one
    of them would reach for every chip and all but the first fail or
    hang. The launcher itself never touches JAX."""
    if (args.nproc_per_node > 1
            and os.environ.get("JAX_PLATFORMS", "") != "cpu"):
        raise SystemExit(
            f"[launch] --nproc_per_node {args.nproc_per_node} refused: "
            "one host is one process driving all its chips, and local "
            "workers are given no chip of their own. Run one process "
            "per host (--nproc_per_node 1), or set JAX_PLATFORMS=cpu "
            "for a multi-process CPU job.")


def launch(argv):
    args = _parse(argv)
    _refuse_shared_chips(args)
    tdir = os.environ.get("PT_TRACE_DIR")
    if tdir:
        # the launcher itself has no PT_PROCESS_ID: its atexit export
        # would land on trace_rank0.json and clobber worker 0's file —
        # repoint it to a launcher-named lane file
        from paddle_tpu.observability import trace as _trace
        _trace._TRACER.out_path = os.path.join(tdir,
                                               "trace_launcher.json")
        # a reused trace dir must not leak a previous (possibly larger)
        # run's rank files into this run's merge as ghost lanes
        import glob as _glob
        for stale in _glob.glob(os.path.join(tdir, "trace_rank*.json")):
            try:
                os.remove(stale)
            except OSError:
                pass
    try:
        if args.elastic:
            return _launch_elastic(args)
        return _launch_static(args)
    finally:
        _merge_traces_on_exit()


def _launch_static(args):
    attempt = 0
    nproc = args.nproc_per_node
    # PT_ELASTIC_RESHAPE=1: the --max_restarts relaunch becomes a local
    # RESHAPE — the group relaunches at the SURVIVING worker count (the
    # failed workers removed) and every worker sees the NEW world size /
    # membership through the standard PT_NUM_PROCESSES / PT_PROCESS_ID
    # contract (until this knob, PT_RESTART_ATTEMPT was the relaunch
    # path's only contract and the world size silently stayed stale).
    # Training scripts built on fleet/elastic_train re-plan their mesh
    # from the new size and restore_resharded onto it. Multi-node
    # membership changes are the --elastic controller's job; this knob
    # covers the single-node preemption (a worker OOM-killed or
    # preempted) without a registry round.
    reshape = (os.environ.get("PT_ELASTIC_RESHAPE", "0") != "0"
               and args.nnodes == 1)
    pure_reforms = 0
    # relaunch generation, exported as PT_RESTART_ATTEMPT: EVERY
    # relaunch — budget-burning failure or pure reshape re-form — must
    # read as a resume to the workers ("attempt 1+ restores"), so this
    # is decoupled from `attempt`, which only counts failures against
    # --max_restarts
    gen = 0
    while True:
        # PT_RESTART_ATTEMPT is the auto-resume contract: workers (re)started
        # by the same launcher see which attempt they are, so training
        # scripts unconditionally AutoCheckpoint.restore() and attempt 1+
        # resumes from the last VERIFIED checkpoint with no operator action
        world = args.nnodes * nproc
        procs = [_spawn(args, i,
                        rank=args.node_rank * nproc + i, world=world,
                        extra_env={"PT_RESTART_ATTEMPT": str(gen)})
                 for i in range(nproc)]
        rc = _watch(procs, coalesce_s=1.0 if reshape else 0.0)
        if rc == 0:
            return 0
        gen += 1
        from paddle_tpu import stats
        if reshape:
            # shrink to the survivors: workers that exited on their own
            # (rc > 0) or were signaled from OUTSIDE (preemption via
            # SIGKILL or SIGTERM, crash via SIGSEGV/SIGABRT, ...) are
            # the failures; workers the launcher itself SIGTERMed —
            # or had to escalate to SIGKILL — during the group kill
            # (both marked in _kill_group) were healthy casualties
            # whatever code they exited with (a SIGTERM handler may
            # clean up and sys.exit(1)). ELASTIC_EXIT_CODE exits are
            # reshape REQUESTS, not failures (the requester rejoins
            # the relaunch).
            n_failed = sum(
                1 for p in procs
                if p.returncode != ELASTIC_EXIT_CODE
                and (p.returncode or 0) != 0
                and not getattr(p, "_pt_launcher_killed", False)
                and not getattr(p, "_pt_launcher_terminated", False))
            n_reshape = sum(1 for p in procs
                            if p.returncode == ELASTIC_EXIT_CODE)
            if n_failed == 0 and n_reshape > 0:
                # pure reshape request: relaunch at the SAME size (the
                # requesting survivors rejoin) and burn NO restart
                # budget — nothing actually failed, matching the
                # elastic path's accounting. Bounded separately so a
                # deterministically recurring request can't loop the
                # launcher forever.
                pure_reforms += 1
                if pure_reforms > max(8, 4 * args.max_restarts):
                    return rc
                print(f"[launch] re-forming same-size group after "
                      f"reshape request (rc={rc})", file=sys.stderr)
                stats.add("launch/restarts")
                continue
            new = max(1, nproc - max(1, n_failed))
            if new != nproc:
                stats.add("launch/reshapes")
                stats.set_value("launch/world_size", args.nnodes * new)
                print(f"[launch] reshaping local group {nproc}->{new} "
                      f"workers after rc={rc}", file=sys.stderr)
                nproc = new
        attempt += 1
        if attempt > args.max_restarts:
            return rc
        stats.add("launch/restarts")
        print(f"[launch] worker failed rc={rc}; restart "
              f"{attempt}/{args.max_restarts}", file=sys.stderr)


def main():
    sys.exit(launch(sys.argv[1:]))


if __name__ == "__main__":
    main()
