"""Training driver with fault tolerance.

Runs any assigned architecture (``--arch``, reduced with ``--smoke``) on the
host's devices; wires together: synthetic sharded data pipeline, jitted
pjit train step (FSDP+TP from the logical rules), async checkpointing with
auto-resume, the FIGMN telemetry anomaly detector (the paper's algorithm —
divergence/straggler alarms) and the straggler monitor with elastic-rescale
hooks.

Example (CPU, end-to-end driver deliverable):
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --steps 200 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, configs
from repro.checkpoint import CheckpointManager
from repro.data.tokens import SyntheticTokens, TokenPipelineConfig
from repro.distributed.sharding import mesh_rules
from repro.ft.anomaly import AnomalyDetector
from repro.ft.straggler import StragglerMonitor
from repro.launch.mesh import host_device_mesh
from repro.models import transformer
from repro.train import optimizer as optim
from repro.train import trainer


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch) if args.smoke \
        else configs.get(args.arch)
    mesh = host_device_mesh(args.model_parallel)
    tcfg = trainer.TrainConfig(
        opt=optim.AdamWConfig(lr_peak=args.lr, warmup_steps=args.steps // 10,
                              total_steps=args.steps),
        microbatches=args.microbatches)

    with mesh_rules(mesh):
        params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_state = optim.init(params)
    print(f"arch={cfg.name} params={transformer.param_count(params):,} "
          f"mesh={dict(mesh.shape)}")

    ckpt = CheckpointManager(os.path.join(args.ckpt_dir, cfg.name))
    start_step = 0
    latest = ckpt.latest_step()
    if latest is not None:
        print(f"auto-resume from step {latest}")
        state = ckpt.restore(latest, {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start_step = latest

    pipe = SyntheticTokens(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed))
    step_fn = trainer.jit_train_step(cfg, tcfg, mesh)

    detector = AnomalyDetector(dim=3)
    monitor = StragglerMonitor(hosts=[f"host{i}" for i in
                                      range(max(jax.process_count(), 1))])
    stop = {"now": False}
    signal.signal(signal.SIGTERM,
                  lambda *_: stop.__setitem__("now", True))

    extras = {}
    if cfg.family == "vlm":
        sv = args.seq // 8
        extras["pixel_embeds"] = jnp.zeros((args.batch, sv, cfg.d_model),
                                           cfg.param_dtype)
        extras["positions3"] = jnp.broadcast_to(
            jnp.arange(args.seq, dtype=jnp.int32), (3, args.batch, args.seq))
    if cfg.is_encdec:
        extras["enc_frames"] = jnp.zeros(
            (args.batch, args.seq // 4, cfg.d_model), cfg.param_dtype)

    t_last = time.time()
    for step in range(start_step, args.steps):
        raw = pipe.batch(step)
        batch = {k: jnp.asarray(v) for k, v in raw.items()}
        batch.update(extras)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        step_time = time.time() - t_last
        t_last = time.time()

        monitor.report("host0", step_time)
        verdict = detector.update({
            "loss": float(metrics["loss"]),
            "grad_norm": float(metrics["grad_norm"]),
            "step_time": step_time,
        })
        if verdict["anomalous"]:
            print(f"[FT] step {step}: telemetry anomaly "
                  f"d2={verdict['d2']:.1f} > {verdict['thresh']:.1f} — "
                  f"checkpointing defensively")
            ckpt.save(step, {"params": params, "opt": opt_state})
        for evicted in monitor.check():
            print(f"[FT] straggler evicted: {evicted} — elastic rescale "
                  f"would restore latest checkpoint on the reduced mesh")

        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} {step_time*1e3:.0f}ms")
        if step and step % args.ckpt_every == 0:
            ckpt.save(step, {"params": params, "opt": opt_state})
        if stop["now"]:
            print("[FT] SIGTERM: preemption checkpoint + exit")
            ckpt.save(step, {"params": params, "opt": opt_state})
            break
    ckpt.wait()
    print("done.")


if __name__ == "__main__":
    main()
