"""Multi-process PAC launcher — one process per host, devices pooled into
one process-spanning "part" axis — with an elastic supervisor mode that
survives host loss.

This is both the reference for launching SPEED's PAC on a pod (one
invocation per host, a coordinator address they all agree on) and the
driver the 2-process CPU-cluster parity test spawns in CI.  Every process
runs the SAME program (standard SPMD): plans only its local devices' rows
(``pac_train`` detects the multi-process mesh), stages them with
``make_array_from_process_local_data``, and the Alg.2 shared-node memory
sync crosses hosts through the mesh collectives.

    # host 0                                       # host 1
    python -m repro.launch.pac_cluster \\
        --num-processes 2 --process-id 0 \\          ... --process-id 1 \\
        --coordinator 10.0.0.1:12321

On CPU the cluster uses the gloo collectives backend and
``--local-devices`` forces that many host devices per process, which is
how CI simulates two hosts on one machine.  ``--out`` dumps losses,
params, merged memories and protocol metrics to an ``.npz`` so runs can
be compared bit-for-bit across process counts.

Elastic mode (``--elastic --run-dir DIR``) splits each invocation into a
SUPERVISOR and a re-execed WORKER subprocess (gloo cannot re-join a
smaller world in-process, so recovery requires a fresh process):

  * the worker heartbeats ``DIR/hb_<rank>`` and a watchdog kills it with
    ``EXIT_PEER_LOST`` when a peer's heartbeat goes stale (a hung
    collective never times out on its own);
  * ``jax.distributed.initialize`` runs under bounded retries with
    exponential backoff + jitter (``--cluster-retries``/``--backoff``),
    logging every attempt — exhaustion exits ``EXIT_UNAVAILABLE``;
  * a worker killed by SIGKILL is treated as a PERMANENTLY lost host
    (simulated preemption): its supervisor marks ``DIR/lost_<rank>`` and
    exits 0;
  * surviving supervisors wait one heartbeat window (refreshing their own
    heartbeat), re-read the survivor set, and relaunch workers over a
    re-ranked world on a fresh coordinator port (``base_port + attempt``)
    with ``--resume``: params/opt state come back from the newest atomic
    checkpoint in ``DIR/ckpt`` and training continues from the next
    epoch — no replay of finished epochs.  ``--max-restarts`` bounds the
    cycles; exhaustion exits ``EXIT_RETRIES_EXHAUSTED``.

Deterministic faults for testing all of this are injected via the
``REPRO_FAULTS`` environment variable (see ``repro.faults``), e.g.
``REPRO_FAULTS=host_kill@epoch=1,rank=1`` SIGKILLs original rank 1 at the
top of epoch 1 — the surviving rank re-forms a 1-process world and
finishes the run.
"""

from __future__ import annotations

import argparse
import os
import random
import signal
import subprocess
import sys
import threading
import time

EXIT_UNAVAILABLE = 17        # the cluster cannot form at all (skip in CI)
EXIT_RETRIES_EXHAUSTED = 18  # elastic restart budget spent
EXIT_PEER_LOST = 23          # a peer died mid-run; supervisor may re-form

_WORKER_ENV = "REPRO_PAC_WORKER"
_RANK_ENV = "REPRO_PAC_ORIG_RANK"


def _parse(argv):
    ap = argparse.ArgumentParser(
        prog="pac_cluster",
        description="multi-process PAC training driver (one per host)")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--coordinator", default="127.0.0.1:12321",
                    help="host:port every process can reach (process 0 "
                         "binds it)")
    ap.add_argument("--local-devices", type=int, default=2,
                    help="force this many CPU devices per process "
                         "(0 = leave XLA_FLAGS alone, e.g. real TPUs)")
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--parts", type=int, default=8,
                    help="SEP partitions; > total devices exercises the "
                         "shuffle-combine resync every epoch")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--grid-layout", default="sharded",
                    choices=["sharded", "replicated"])
    ap.add_argument("--sync-mode", default="latest",
                    choices=["latest", "mean"])
    ap.add_argument("--epoch-boundary", default="overlap",
                    choices=["overlap", "serial"],
                    help="'overlap' pipelines the Alg.2 memory sync and "
                         "loss reads behind the next epoch; 'serial' is "
                         "the fused bit-parity oracle")
    ap.add_argument("--eval-warm", default="memory",
                    choices=["memory", "replay", "restart"],
                    help="where the eval protocol's warm memory comes "
                         "from: PAC's synced memories, a train-split "
                         "replay, or the TIGER-style restarter head")
    ap.add_argument("--out", default="",
                    help="write losses/params/memory/metrics to this .npz")
    # --- fault tolerance ---------------------------------------------
    ap.add_argument("--elastic", action="store_true",
                    help="supervise a re-execed worker: on host loss, "
                         "re-form the world over the survivors and resume "
                         "from the latest checkpoint")
    ap.add_argument("--run-dir", default="",
                    help="shared scratch dir for heartbeats, loss markers "
                         "and checkpoints (required with --elastic)")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="checkpoint {params, opt_state, states} every "
                         "this many epochs (0 = off; needs --run-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="restore from the newest checkpoint in "
                         "run-dir/ckpt before training")
    ap.add_argument("--max-restarts", type=int, default=2,
                    help="elastic re-formation cycles before giving up")
    ap.add_argument("--cluster-retries", type=int, default=3,
                    help="jax.distributed.initialize attempts per worker")
    ap.add_argument("--backoff", type=float, default=0.5,
                    help="base of the exponential retry backoff, seconds")
    ap.add_argument("--heartbeat-interval", type=float, default=0.25)
    ap.add_argument("--heartbeat-timeout", type=float, default=10.0,
                    help="a peer whose heartbeat is older than this is "
                         "declared lost")
    # internal (set by the supervisor on re-exec)
    ap.add_argument("--orig-rank", type=int, default=-1,
                    help=argparse.SUPPRESS)
    ap.add_argument("--peers", default="", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


# --- run-dir markers ---------------------------------------------------

def _hb(run_dir, rank):
    return os.path.join(run_dir, f"hb_{rank}")


def _done(run_dir, rank):
    return os.path.join(run_dir, f"done_{rank}")


def _lost(run_dir, rank):
    return os.path.join(run_dir, f"lost_{rank}")


def _touch(path):
    with open(path, "w") as f:
        f.write(f"{time.time()}\n")


def _age(path) -> float:
    try:
        return time.time() - os.path.getmtime(path)
    except OSError:
        return float("inf")


# --- supervisor --------------------------------------------------------

def _supervise(args) -> int:
    """Run (and re-run) the worker subprocess for ONE original rank.

    Every host runs one supervisor; they coordinate purely through the
    shared ``--run-dir`` (heartbeat freshness + ``lost_<rank>`` markers)
    and the deterministic port schedule ``base_port + attempt`` — no
    control plane of its own, so the supervisor survives anything short
    of the host itself dying (which IS the case it exists to report)."""
    if not args.run_dir:
        print("ELASTIC: --elastic requires --run-dir", flush=True)
        return 2
    os.makedirs(args.run_dir, exist_ok=True)
    host, _, port_s = args.coordinator.rpartition(":")
    base_port = int(port_s)
    rank = args.process_id
    world = list(range(args.num_processes))
    # keep the TOTAL device count (and with it every epoch plan) fixed as
    # the world shrinks: survivors pick up the lost host's device slots,
    # so a recovered run is numerically the same schedule as an
    # undisturbed one (0 = real accelerators, nothing to scale)
    total_devices = args.num_processes * args.local_devices
    _touch(_hb(args.run_dir, rank))

    for attempt in range(args.max_restarts + 1):
        slot = world.index(rank)
        local = total_devices // len(world) if args.local_devices else 0
        cmd = [
            sys.executable, "-m", "repro.launch.pac_cluster",
            "--num-processes", str(len(world)),
            "--process-id", str(slot),
            "--coordinator", f"{host}:{base_port + attempt}",
            "--local-devices", str(local),
            "--epochs", str(args.epochs),
            "--parts", str(args.parts),
            "--seed", str(args.seed),
            "--grid-layout", args.grid_layout,
            "--sync-mode", args.sync_mode,
            "--epoch-boundary", args.epoch_boundary,
            "--eval-warm", args.eval_warm,
            "--run-dir", args.run_dir,
            "--ckpt-every", str(args.ckpt_every),
            "--cluster-retries", str(args.cluster_retries),
            "--backoff", str(args.backoff),
            "--heartbeat-interval", str(args.heartbeat_interval),
            "--heartbeat-timeout", str(args.heartbeat_timeout),
            "--orig-rank", str(rank),
            "--peers", ",".join(map(str, world)),
        ]
        if args.out:
            cmd += ["--out", args.out]
        if args.resume or attempt > 0:
            cmd.append("--resume")
        env = dict(os.environ)
        env[_WORKER_ENV] = "1"
        env[_RANK_ENV] = str(rank)
        print(f"ELASTIC: attempt {attempt}/{args.max_restarts}: rank "
              f"{rank} -> slot {slot} of world {world} on port "
              f"{base_port + attempt}", flush=True)
        rc = subprocess.Popen(cmd, env=env).wait()

        if rc == 0:
            return 0
        if rc == EXIT_UNAVAILABLE:
            print("ELASTIC: worker reported the cluster unavailable",
                  flush=True)
            return EXIT_UNAVAILABLE
        if rc == -signal.SIGKILL:
            # simulated preemption / OOM-kill: THIS host is the lost one.
            # Mark it permanently dead and bow out cleanly — the
            # survivors re-form without us.
            _touch(_lost(args.run_dir, rank))
            try:
                os.remove(_hb(args.run_dir, rank))
            except OSError:
                pass
            print(f"ELASTIC: rank {rank} HOST_LOST (worker SIGKILLed)",
                  flush=True)
            return 0
        if rc > 0 and rc != EXIT_PEER_LOST:
            return rc  # a real worker bug: don't mask it with retries

        # EXIT_PEER_LOST (or a startup-skew signal): wait one full
        # heartbeat window — refreshing OUR heartbeat so the other
        # survivors keep counting us — then re-read the survivor set.
        delay = max(args.heartbeat_timeout + 2 * args.heartbeat_interval,
                    args.backoff * (2 ** attempt))
        delay += random.uniform(0, args.heartbeat_interval)
        print(f"ELASTIC: rank {rank} worker exited rc={rc}; re-forming "
              f"in {delay:.1f}s", flush=True)
        deadline = time.time() + delay
        while time.time() < deadline:
            _touch(_hb(args.run_dir, rank))
            time.sleep(min(args.heartbeat_interval,
                           max(0.0, deadline - time.time())))
        world = [r for r in world
                 if r == rank or (
                     not os.path.exists(_lost(args.run_dir, r))
                     and _age(_hb(args.run_dir, r)) <
                     args.heartbeat_timeout)]
        print(f"ELASTIC: survivors = {world}", flush=True)

    print(f"ELASTIC: rank {rank} RETRIES_EXHAUSTED after "
          f"{args.max_restarts + 1} attempts", flush=True)
    return EXIT_RETRIES_EXHAUSTED


# --- worker ------------------------------------------------------------

def _start_heartbeat(run_dir: str, rank: int, interval: float) -> None:
    _touch(_hb(run_dir, rank))

    def beat():
        while True:
            time.sleep(interval)
            try:
                _touch(_hb(run_dir, rank))
            except OSError:
                return

    threading.Thread(target=beat, daemon=True).start()


def _start_watchdog(run_dir: str, rank: int, peers: list[int],
                    interval: float, timeout: float) -> None:
    """Kill THIS worker (``EXIT_PEER_LOST``) when a peer stops
    heartbeating without a ``done`` marker (a peer marked lost has had its
    heartbeat removed): a SIGKILLed peer leaves the survivors hung inside
    a gloo collective that may never error out, so liveness has to come
    from outside the collective stack."""
    started = time.time()

    def watch():
        while True:
            time.sleep(interval)
            for p in peers:
                if p == rank or os.path.exists(_done(run_dir, p)):
                    continue
                age = _age(_hb(run_dir, p))
                if age > timeout and time.time() - started > timeout:
                    print(f"PEER_LOST: rank {p} heartbeat stale "
                          f"({age:.1f}s) — aborting rank {rank}",
                          flush=True)
                    os._exit(EXIT_PEER_LOST)

    threading.Thread(target=watch, daemon=True).start()


def _init_with_retry(args) -> bool:
    """``jax.distributed.initialize`` under bounded retries with
    exponential backoff + jitter; every attempt is logged.  Returns False
    (after printing the ``CLUSTER_UNAVAILABLE`` marker CI keys off) when
    the retry budget is spent."""
    import jax

    # CPU collectives span processes through gloo; TPU pods skip this
    # (the default backend already crosses hosts)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if args.run_dir:
        # elastic worker: a lost peer must reach the re-rank path (watchdog
        # or HostLossError -> EXIT_PEER_LOST), not have the coordination
        # service terminate this process
        jax.config.update("jax_enable_recoverability", True)
    last = None
    for i in range(max(1, args.cluster_retries)):
        try:
            jax.distributed.initialize(
                coordinator_address=args.coordinator,
                num_processes=args.num_processes,
                process_id=args.process_id,
                initialization_timeout=60)
            return True
        except Exception as e:  # noqa: BLE001 — every failure retries
            last = e
            print(f"CLUSTER_ATTEMPT {i + 1}/{args.cluster_retries} "
                  f"failed: {type(e).__name__}: {e}", flush=True)
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
            if i + 1 < max(1, args.cluster_retries):
                time.sleep(args.backoff * (2 ** i)
                           + random.uniform(0, args.backoff))
    print(f"CLUSTER_UNAVAILABLE: {type(last).__name__}: {last}",
          flush=True)
    return False


def _run(args) -> int:
    if args.local_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.local_devices}")

    orig_rank = args.orig_rank if args.orig_rank >= 0 else args.process_id
    peers = [int(p) for p in args.peers.split(",") if p != ""]
    if args.run_dir:
        os.makedirs(args.run_dir, exist_ok=True)
        _start_heartbeat(args.run_dir, orig_rank, args.heartbeat_interval)

    import jax

    from repro.launch.cache import setup_compile_cache

    setup_compile_cache()
    if args.num_processes > 1 and not _init_with_retry(args):
        return EXIT_UNAVAILABLE
    if args.run_dir and len(peers) > 1:
        _start_watchdog(args.run_dir, orig_rank, peers,
                        args.heartbeat_interval, args.heartbeat_timeout)

    import numpy as np

    from repro.core import sep_partition
    from repro.faults import HostLossError, is_host_loss
    from repro.launch.mesh import make_tig_mesh
    from repro.tig.data import synthetic_tig
    from repro.tig.distributed import pac_train
    from repro.tig.graph import chronological_split
    from repro.tig.models import TIGConfig

    g = synthetic_tig("tiny", seed=args.seed)
    train_g, _, _, _ = chronological_split(g)
    cfg = TIGConfig(flavor="tgn", dim=16, dim_time=8, dim_edge=16,
                    dim_node=16, num_neighbors=4, batch_size=50)
    part = sep_partition(train_g.src, train_g.dst, train_g.t, g.num_nodes,
                         args.parts, k=0.05)
    mesh = make_tig_mesh()
    n_dev = int(mesh.devices.size)
    ckpt_dir = os.path.join(args.run_dir, "ckpt") if args.run_dir else None

    try:
        res = pac_train(
            train_g, part, cfg, num_devices=n_dev, epochs=args.epochs,
            seed=args.seed, shuffle_parts=True, sync_mode=args.sync_mode,
            mesh=mesh, plan="device", grid_layout=args.grid_layout,
            epoch_boundary=args.epoch_boundary, eval_graph=g,
            eval_warm=args.eval_warm, ckpt_dir=ckpt_dir,
            ckpt_every=args.ckpt_every if ckpt_dir else 0,
            resume=args.resume and ckpt_dir is not None)
    except HostLossError as e:
        print(f"PEER_LOST: {e}", flush=True)
        return EXIT_PEER_LOST
    except Exception as e:  # noqa: BLE001 — classified below
        if args.num_processes > 1 and is_host_loss(e):
            print(f"PEER_LOST: {type(e).__name__}: {e}", flush=True)
            return EXIT_PEER_LOST
        raise

    if args.out:
        payload = {}
        for e, losses in enumerate(res.losses):
            payload[f"loss_{e}"] = np.asarray(losses)
        # tree_leaves order is deterministic for a fixed param structure
        for i, leaf in enumerate(jax.tree_util.tree_leaves(res.params)):
            payload[f"param_{i}"] = np.asarray(leaf)
        for key in ("mem", "mem2", "last"):
            payload[f"state_{key}"] = np.asarray(res.memory_states[key])
        for key, val in sorted((res.metrics or {}).items()):
            payload[f"metric_{key}"] = np.asarray(val)
        np.savez(args.out, **payload)

    print(f"pac_cluster done: process {jax.process_index()}"
          f"/{jax.process_count()}, devices={n_dev}, "
          f"grid_layout={args.grid_layout}", flush=True)
    if args.run_dir:
        _touch(_done(args.run_dir, orig_rank))
    if args.num_processes > 1:
        # explicit teardown: the atexit shutdown can race the coordinator
        # when processes finish at different times (SIGABRT on slow hosts)
        try:
            jax.distributed.shutdown()
        except Exception as e:  # noqa: BLE001 — peers may already be gone
            print(f"shutdown raced: {type(e).__name__}: {e}", flush=True)
    return 0


def main(argv=None) -> int:
    args = _parse(argv)
    if args.elastic and os.environ.get(_WORKER_ENV) != "1":
        return _supervise(args)
    return _run(args)


if __name__ == "__main__":
    sys.exit(main())
