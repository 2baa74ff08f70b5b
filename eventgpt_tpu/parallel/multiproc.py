"""Multi-process dry run: the distributed stack across real OS processes.

Everything else in the test/dryrun surface runs ONE process with N virtual
devices, which never exercises a process boundary. This module is the proof
that the pieces of SURVEY §2.4/§5's distributed story actually compose across
processes the way the reference's NCCL/mpi4py/DeepSpeed stack did
(``/root/reference/requirements.txt:85,65,21`` — one rank per GPU, collective
gradient reduction, rank-0-gated artifact writes):

  * ``initialize_distributed`` (``parallel/dist.py``) bootstraps N processes
    through the ``EGPT_*`` env contract against a real coordinator;
  * a ``Mesh`` spanning both processes runs the stage-2 train step, with the
    gradient psum riding cross-process collectives (Gloo on CPU — the same
    pjit program that rides ICI on a pod);
  * the loss matches a single-process run of the identical global program;
  * checkpoints are written the trainer's way — orbax save as a collective,
    ``STEP``/component files gated by ``is_primary()`` — and restored on the
    *other* rank;
  * a preemption signal landing on ONE rank propagates through
    ``GracefulShutdown.globally_requested()``'s allgather so BOTH ranks take
    a coordinated checkpoint (``train/resilience.py`` — the mismatched-
    collective deadlock this prevents only exists with >= 2 processes).

Topology: ``n_processes`` workers x ``local_devices`` virtual CPU devices
each, so 2 x 8 doubles as the 16-device mesh proof. The launcher runs the
workers plus a single-process reference job and compares losses.
"""

from __future__ import annotations

import functools
import json
import os
import socket
import subprocess
import sys
import tempfile
from typing import Dict, List, Optional, Sequence

# Parsed by the launcher from worker stdout; versioned so stale workers fail
# loudly rather than mis-parse.
_RESULT_TAG = "MPRESULT1"


def _reserve_port() -> socket.socket:
    """Bind an ephemeral port and HOLD the socket (ADVICE r5: closing
    before the coordinator binds leaves a window where another process
    claims the port — a spurious bootstrap failure under parallel CI).
    The caller closes it just before spawning workers; SO_REUSEADDR lets
    the coordinator rebind the briefly-TIME_WAIT-free port immediately."""
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    return s


# ---------------------------------------------------------------------------
# Worker (child process) side


def _put_global(tree, specs, mesh):
    """Host pytree -> global sharded arrays, multi-process safe.

    ``jax.device_put`` onto a sharding with non-addressable devices is not
    portable; ``make_array_from_callback`` is — every process holds the full
    host value (same seed everywhere) and contributes its addressable shards.
    """
    import jax
    import numpy as np

    from eventgpt_tpu.parallel.sharding import tree_shardings

    shardings = tree_shardings(specs, mesh)

    def put(x, s):
        x = np.asarray(jax.device_get(x))
        return jax.make_array_from_callback(x.shape, s, lambda idx: x[idx])

    return jax.tree_util.tree_map(put, tree, shardings)


@functools.lru_cache(maxsize=8)
def _gather_jit(rep):
    """One replicate-to-host executable per target sharding — rebuilding
    ``jax.jit(lambda ...)`` inside ``gather`` re-traced per LEAF (the
    jit-hygiene rule's untracked-creation case); shardings are hashable,
    so the lru key is the executable's identity."""
    import jax

    return jax.jit(lambda v: v, out_shardings=rep)


def _replicate_to_host(tree):
    """Gather a (possibly cross-process) sharded pytree to host numpy on
    every process: jit to a fully-replicated layout, then device_get."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def gather(x):
        mesh = x.sharding.mesh
        rep = NamedSharding(mesh, P())
        return jax.device_get(_gather_jit(rep)(x))

    return jax.tree_util.tree_map(gather, tree)


def worker_main() -> None:
    """Entry for both the multi-process workers and the single-process
    reference job (distinguished by the presence of the EGPT_* contract)."""
    # Workers simulate standalone hosts: the launcher scrubs the ambient
    # pod-autodetect vars from their environment (_worker_env).
    from eventgpt_tpu import faults

    # Chaos hook for the process-boundary story: EGPT_FAULTS propagates
    # through the spawn env, so 'multiproc.worker:n=1' kills the first
    # worker's bootstrap — the launcher's round-robin poll must surface
    # it as that rank's failure, not a coordinator deadlock.
    faults.maybe_fail("multiproc.worker")
    import jax

    # The launcher sets JAX_PLATFORMS=cpu (_worker_env); the config update
    # also covers a worker started by hand, and must land before backend
    # init.
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")

    import numpy as np

    from eventgpt_tpu import checkpoint as ckpt
    from eventgpt_tpu.config import EventChatConfig, MeshConfig
    from eventgpt_tpu.models import eventchat
    from eventgpt_tpu.parallel import make_mesh
    from eventgpt_tpu.parallel.dist import barrier, initialize_distributed, is_primary
    from eventgpt_tpu.parallel.sharding import (
        batch_spec, clip_param_specs, llama_param_specs, projector_param_specs,
    )
    from eventgpt_tpu.train import steps as steps_mod
    from eventgpt_tpu.train.data import synthetic_multimodal_batch
    from eventgpt_tpu.train.lora import LoraConfig, lora_param_specs
    from eventgpt_tpu.train.optim import linear_warmup_cosine, make_optimizer
    from eventgpt_tpu.train.resilience import GracefulShutdown

    multi = initialize_distributed()
    rank = jax.process_index()
    nproc = jax.process_count()

    # Per-process metric labels (ISSUE 3 / DISTRIBUTED.md): every sample a
    # worker exposes (or dumps into telemetry.jsonl) carries its rank, so
    # scrapes from N processes on one host stay disambiguated without any
    # name mangling. The same call is the pattern for real pod launches.
    from eventgpt_tpu.obs import metrics as _obs_metrics

    _obs_metrics.REGISTRY.set_common_labels(process=str(rank))

    mesh_shape = [int(x) for x in os.environ["EGPT_MP_MESH"].split(",")]
    n_steps = int(os.environ.get("EGPT_MP_STEPS", "2"))
    outdir = os.environ["EGPT_MP_OUTDIR"]
    attn_impl = os.environ.get("EGPT_MP_ATTN", "dense")

    mcfg = MeshConfig(data=mesh_shape[0], fsdp=mesh_shape[1],
                      context=mesh_shape[2], model=mesh_shape[3])
    mesh = make_mesh(mcfg)  # all global devices — spans both processes

    import dataclasses

    cfg = EventChatConfig.tiny()
    cfg = dataclasses.replace(
        cfg, llama=dataclasses.replace(cfg.llama, attn_impl=attn_impl))

    params = eventchat.init_eventchat_params(cfg, jax.random.PRNGKey(0))
    lcfg = LoraConfig(r=4)
    trainable, frozen = steps_mod.split_stage2(
        params, cfg, lcfg, jax.random.PRNGKey(1))
    trainable = _put_global(
        trainable,
        {"projector": projector_param_specs(
            cfg.projector.use_feature_adaptor, cfg.projector.mlp_depth),
         "lora": lora_param_specs(lcfg.targets)},
        mesh)
    frozen = _put_global(
        frozen, {"clip": clip_param_specs(), "llama": llama_param_specs()},
        mesh)

    opt = make_optimizer(linear_warmup_cosine(1e-3, 10, 0))
    state = steps_mod.init_train_state(trainable, frozen, opt)
    step_fn = steps_mod.make_train_step(
        cfg, opt, steps_mod.make_stage2_combine(lcfg), donate=False, mesh=mesh)

    batch_size = mcfg.data * mcfg.fsdp
    host_batch = synthetic_multimodal_batch(cfg, batch_size, 64, event_offset=8)
    ctx = mesh.shape["context"]
    batch = _put_global(
        host_batch,
        {k: batch_spec(
            np.ndim(v),
            seq_axis=1 if np.ndim(v) == 2 and v.shape[1] % ctx == 0 else None)
         for k, v in host_batch.items()},
        mesh)

    losses: List[float] = []
    for _ in range(n_steps):
        state, metrics = step_fn(state, batch)
        losses.append(float(jax.device_get(metrics["loss"])))
    if any(l != l for l in losses):
        raise RuntimeError(f"rank {rank}: NaN loss in multiproc dry run: {losses}")

    resumed_ok: Optional[bool] = None
    preempt_line = ""
    if multi:
        # --- Checkpoint leg: the trainer's exact write discipline ---------
        # orbax save is a collective (every process writes its shards);
        # STEP is primary-only (trainer.save, train/trainer.py:356-368).
        ckpt_dir = os.path.join(outdir, "ckpt_mp")
        ckpt.save_checkpoint(ckpt_dir, {"trainable": state.trainable,
                                        "step": state.step})
        if is_primary():
            with open(os.path.join(ckpt_dir, "STEP"), "w") as f:
                f.write(str(int(jax.device_get(state.step))))
        barrier("ckpt_mp_written")

        # Resume on the NON-primary rank: restore into the live shardings
        # and verify the restored tree matches what this rank holds.
        restored = ckpt.load_checkpoint(
            ckpt_dir, target={"trainable": state.trainable, "step": state.step})
        live = _replicate_to_host(state.trainable)
        back = _replicate_to_host(restored["trainable"])
        flat_live = jax.tree_util.tree_leaves(live)
        flat_back = jax.tree_util.tree_leaves(back)
        resumed_ok = (
            int(jax.device_get(restored["step"])) == n_steps
            and len(flat_live) == len(flat_back)
            and all(np.array_equal(a, b) for a, b in zip(flat_live, flat_back))
        )
        if not resumed_ok:
            raise RuntimeError(
                f"rank {rank}: restored checkpoint diverges from live state")

        # --- Preemption leg ------------------------------------------------
        # SIGTERM lands on ONE host (rank 1 here, via the programmatic
        # trigger the fault-injection tests use); every rank must agree
        # through the allgather before touching a collective save.
        shutdown = GracefulShutdown()
        if rank == 1:
            shutdown.request("simulated-preemption")
        agreed = shutdown.globally_requested()
        if not agreed:
            raise RuntimeError(
                f"rank {rank}: preemption allgather missed the rank-1 signal")
        if rank == 0 and shutdown.requested:
            raise RuntimeError("rank 0 local flag set — test wiring broken")
        # Coordinated checkpoint: both ranks enter the same collective.
        pre_dir = os.path.join(outdir, "ckpt_preempt_mp")
        ckpt.save_checkpoint(pre_dir, {"trainable": state.trainable,
                                       "step": state.step})
        if is_primary():
            with open(os.path.join(pre_dir, "STEP"), "w") as f:
                f.write(str(int(jax.device_get(state.step))))
        barrier("preempt_ckpt_written")
        if not os.path.isdir(pre_dir):
            raise RuntimeError(f"rank {rank}: coordinated checkpoint missing")
        preempt_line = (
            f"local_flag(rank{rank})={shutdown.requested} agreed={agreed}")

    print(_RESULT_TAG + json.dumps({
        "rank": rank, "n_processes": nproc,
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
        "mesh": {"data": mcfg.data, "fsdp": mcfg.fsdp,
                 "context": mcfg.context, "model": mcfg.model},
        "attn": attn_impl, "losses": losses,
        "resumed_ok": resumed_ok, "preempt": preempt_line,
    }), flush=True)


# ---------------------------------------------------------------------------
# Launcher (parent) side


def _worker_env(base: Dict[str, str], local_devices: int) -> Dict[str, str]:
    env = dict(base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={local_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    # A worker must never inherit a half-set contract from the caller, nor
    # the ambient pod-autodetect vars (a TPU VM exports
    # TPU_WORKER_HOSTNAMES, which would push the single-process reference
    # job into jax.distributed.initialize with no coordinator).
    from eventgpt_tpu.parallel.dist import POD_AUTODETECT_VARS

    for k in ("EGPT_COORDINATOR", "EGPT_NUM_PROCESSES",
              "EGPT_PROCESS_ID") + POD_AUTODETECT_VARS:
        env.pop(k, None)
    return env


def _parse_result(stdout: str, who: str) -> dict:
    for line in stdout.splitlines():
        if line.startswith(_RESULT_TAG):
            return json.loads(line[len(_RESULT_TAG):])
    raise RuntimeError(f"{who}: no {_RESULT_TAG} line in output:\n{stdout[-2000:]}")


def launch_multiprocess_dryrun(
    n_processes: int = 2,
    local_devices: int = 8,
    mesh_shape: Sequence[int] = (2, 2, 2, 2),
    n_steps: int = 2,
    attn_impl: str = "ring",
    timeout: float = 1500.0,
    rtol: float = 1e-5,
) -> dict:
    """Run the multi-process dry run + single-process reference; compare.

    Returns the summary dict (also printed as artifact lines). Raises on any
    worker failure, loss mismatch, or missing leg.
    """
    import math

    global_devices = n_processes * local_devices
    if math.prod(mesh_shape) != global_devices:
        raise ValueError(f"mesh {tuple(mesh_shape)} needs "
                         f"{math.prod(mesh_shape)} devices, have "
                         f"{n_processes}x{local_devices}={global_devices}")

    from eventgpt_tpu import faults

    faults.maybe_fail("multiproc.launch")
    port_sock = _reserve_port()
    port = port_sock.getsockname()[1]
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    cmd = [sys.executable, "-m", "eventgpt_tpu.parallel.multiproc", "--worker"]

    with tempfile.TemporaryDirectory(prefix="egpt_mp_") as outdir:
        common = {
            "EGPT_MP_MESH": ",".join(str(x) for x in mesh_shape),
            "EGPT_MP_STEPS": str(n_steps),
            "EGPT_MP_OUTDIR": outdir,
            "EGPT_MP_ATTN": attn_impl,
        }
        # Worker output goes to FILES, not pipes: a rank blocked writing
        # into an undrained 64 KiB pipe would stall out of its collectives
        # — turning any verbose crash into a generic cross-rank timeout —
        # and files let the poll loop below read everything post-mortem.
        procs = []
        logs = []
        # Release the reserved port at the last possible moment: the
        # rank-0 worker's coordinator binds it next.
        port_sock.close()
        for rank in range(n_processes):
            env = _worker_env(os.environ, local_devices)
            env.update(common)
            env["EGPT_COORDINATOR"] = f"127.0.0.1:{port}"
            env["EGPT_NUM_PROCESSES"] = str(n_processes)
            env["EGPT_PROCESS_ID"] = str(rank)
            out_path = os.path.join(outdir, f"rank{rank}.out")
            err_path = os.path.join(outdir, f"rank{rank}.err")
            logs.append((out_path, err_path))
            with open(out_path, "w") as fo, open(err_path, "w") as fe:
                procs.append(subprocess.Popen(
                    cmd, env=env, cwd=repo, stdout=fo, stderr=fe))
        # Round-robin poll rather than sequential waits: whichever rank
        # dies first must surface immediately — its survivors are blocked
        # in collectives that can never complete, and a sequential wait on
        # a lower-indexed survivor would burn the whole timeout and then
        # misreport the crash as a coordinator deadlock.
        import time as _time

        deadline = _time.monotonic() + timeout
        pending = set(range(n_processes))
        failed_rank = None
        while pending:
            for rank in sorted(pending):
                rc = procs[rank].poll()
                if rc is None:
                    continue
                pending.discard(rank)
                if rc != 0 and failed_rank is None:
                    failed_rank = rank
            if failed_rank is not None and pending:
                # Short grace for survivors, then put them down.
                grace = _time.monotonic() + 5.0
                while pending and _time.monotonic() < grace:
                    for rank in list(pending):
                        if procs[rank].poll() is not None:
                            pending.discard(rank)
                    _time.sleep(0.1)
                for rank in pending:
                    procs[rank].kill()
                    procs[rank].wait()
                pending.clear()
            elif pending:
                if _time.monotonic() > deadline:
                    stuck = sorted(pending)
                    for q in procs:
                        q.kill()
                        q.wait()  # reap before reading logs (no zombies)
                    tails = []
                    for rank in stuck:
                        try:
                            with open(logs[rank][1]) as fe:
                                tails.append(f"-- rank {rank} stderr --\n"
                                             f"{fe.read()[-1000:]}")
                        except OSError:
                            pass
                    raise RuntimeError(
                        f"multiproc ranks {stuck} still running after "
                        f"{timeout}s (coordinator deadlock?)\n"
                        + "\n".join(tails))
                _time.sleep(0.2)
        outs = []
        for rank in range(n_processes):
            with open(logs[rank][0]) as fo, open(logs[rank][1]) as fe:
                outs.append((fo.read(), fe.read()))
        if failed_rank is not None:
            raise RuntimeError(
                f"multiproc worker rank {failed_rank} failed "
                f"(rc={procs[failed_rank].returncode}):\n"
                f"{outs[failed_rank][1][-3000:]}")
        results = [_parse_result(out, f"rank {i}") for i, (out, _) in enumerate(outs)]

        # Single-process reference: the identical global program on one
        # process with all devices local (no EGPT_* contract -> fast path).
        env = _worker_env(os.environ, global_devices)
        env.update(common)
        ref_proc = subprocess.run(
            cmd, env=env, cwd=repo, capture_output=True, text=True,
            timeout=timeout)
        if ref_proc.returncode != 0:
            raise RuntimeError(
                f"single-process reference failed (rc={ref_proc.returncode}):\n"
                f"{ref_proc.stderr[-3000:]}")
        ref = _parse_result(ref_proc.stdout, "single-process reference")

    by_rank = {r["rank"]: r for r in results}
    losses_mp = by_rank[0]["losses"]
    losses_ref = ref["losses"]
    for i, (a, b) in enumerate(zip(losses_mp, losses_ref)):
        if not math.isclose(a, b, rel_tol=rtol, abs_tol=0.0):
            raise RuntimeError(
                f"multiproc loss diverges from single-process at step {i}: "
                f"{a!r} vs {b!r} (rtol {rtol})")
    for r in results:
        if r["n_processes"] != n_processes or not r["resumed_ok"]:
            raise RuntimeError(f"bad worker result: {r}")
        if "agreed=True" not in r["preempt"]:
            raise RuntimeError(f"preemption leg missing on rank {r['rank']}: {r}")

    mesh = by_rank[0]["mesh"]
    summary = {
        "n_processes": n_processes, "local_devices": local_devices,
        "global_devices": by_rank[0]["global_devices"], "mesh": mesh,
        "attn": attn_impl, "losses_multiproc": losses_mp,
        "losses_single_process": losses_ref, "rtol": rtol,
    }
    print(f"dryrun_multiproc: n_processes={n_processes} x "
          f"local_devices={local_devices} = {summary['global_devices']} "
          f"global devices, mesh={mesh} attn={attn_impl}: "
          f"loss {losses_mp} == single-process {losses_ref} (rtol {rtol})")
    print("dryrun_multiproc: orbax checkpoint saved collectively, STEP "
          "primary-only, restored + verified on every rank incl. non-primary")
    print("dryrun_multiproc: preemption on rank 1 only -> "
          "GracefulShutdown.globally_requested() allgather agreed on all "
          "ranks -> coordinated checkpoint on both")
    return summary


def main(argv: Optional[List[str]] = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "--worker":
        worker_main()
        return
    launch_multiprocess_dryrun()


if __name__ == "__main__":
    main()
