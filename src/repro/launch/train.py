"""End-to-end training driver with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch jedinet-30p \
        --steps 300 --batch 512 --ckpt-dir /tmp/ckpt

Production behaviours exercised here at container scale:

* **Checkpoint/restart** — async checkpoint every ``--ckpt-every`` steps;
  on start, the latest checkpoint under ``--ckpt-dir`` is restored
  (elastic: onto whatever mesh exists now).
* **Preemption safety** — SIGTERM/SIGINT trigger a final synchronous
  checkpoint before exit (the SLURM/Borg preemption contract).
* **Failure injection** — ``--fail-at-step N`` raises mid-run to
  demonstrate restart-from-checkpoint (used by the fault-tolerance test).
* **Straggler mitigation** — the input pipeline runs a prefetch thread with
  a bounded queue: a slow host overlaps data generation with device steps
  instead of stalling them.
"""

from __future__ import annotations

import argparse
import queue
import signal
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np


def prefetch(it, depth: int = 2):
    """Bounded-queue background prefetch (straggler overlap)."""
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def worker():
        for item in it:
            if stop.is_set():
                return
            q.put(item)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            yield q.get()
    finally:
        stop.set()


def make_program(arch_id: str, batch: int, lr: float):
    """(init_fn, step_fn, batch_iter, to_device) for a trainable arch."""
    from repro.configs.registry import get_arch
    from repro.training import make_optimizer, make_train_step
    from repro.training.schedule import warmup_cosine, wsd

    arch = get_arch(arch_id)

    if arch.family == "jedinet":
        from repro.core import interaction_net as inet
        from repro.data.jets import jet_batches
        cfg = arch.model
        opt = make_optimizer("adamw", warmup_cosine(lr, 50, 5000))
        return (
            lambda k: inet.init(k, cfg),
            make_train_step(lambda p, b: inet.loss_fn(p, cfg, b), opt),
            jet_batches(0, batch, cfg.n_objects, cfg.n_features),
            opt,
        )
    if arch.family == "lm":
        from repro.models import transformer as tfm
        from repro.data.lm_data import lm_batches
        cfg = arch.model
        # schedule: minicpm uses its signature WSD schedule
        sched = (wsd(lr, 50, 5000) if arch.arch_id == "minicpm-2b"
                 else warmup_cosine(lr, 50, 5000))
        opt = make_optimizer("adafactor", sched)
        return (
            lambda k: tfm.init(k, cfg),
            make_train_step(
                lambda p, b: tfm.loss_fn(p, cfg, b, logit_chunk=None), opt),
            lm_batches(0, batch, 256, cfg.vocab_size),
            opt,
        )
    if arch.family == "recsys":
        from repro.models import recsys as fm_lib
        from repro.data.recsys_data import ctr_batches
        cfg = arch.model
        opt = make_optimizer("adamw", warmup_cosine(lr, 50, 5000),
                             weight_decay=0.0)
        return (
            lambda k: fm_lib.init(k, cfg),
            make_train_step(lambda p, b: fm_lib.loss_fn(p, cfg, b), opt),
            ctr_batches(0, batch, cfg.vocab_sizes),
            opt,
        )
    if arch.family == "gnn":
        from repro.models.gnn import GNN_MODULES
        from repro.data.graphs import community_graph
        from repro.launch.steps import _gnn_loss
        cfg = arch.model
        mod = GNN_MODULES[cfg.kind]
        g = community_graph(0, 4096, 16384, 64, n_classes=cfg.n_classes)
        if cfg.kind in ("meshgraphnet", "equiformer_v2"):
            rngp = np.random.RandomState(1)
            g["pos"] = rngp.normal(0, 1, (4096, 3)).astype(np.float32)
            if cfg.kind == "meshgraphnet":
                g["y"] = np.tanh(g["pos"]).astype(np.float32)
            else:
                g["y"] = np.tanh(g["pos"]).sum(-1).astype(np.float32)
        opt = make_optimizer("adamw", warmup_cosine(lr, 50, 5000))

        def loss_fn(p, batch):
            out = mod.apply(p, cfg, batch)
            return _gnn_loss(cfg.kind, cfg, out, batch)

        def rep(d):
            while True:
                yield d

        return (
            lambda k: mod.init(k, cfg, 64, cfg.n_classes),
            make_train_step(loss_fn, opt),
            rep(g),
            opt,
        )
    raise ValueError(f"no train program for {arch_id}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a crash (fault-tolerance demo)")
    args = ap.parse_args(argv)

    from repro.training import init_state

    init_fn, step_fn, batches, opt = make_program(
        args.arch, args.batch, args.lr)
    step_jit = jax.jit(step_fn)

    cm = None
    state = None
    start_step = 0
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointManager
        cm = CheckpointManager(args.ckpt_dir)
        if cm.latest_step() is not None:
            state, start_step = cm.restore()
            state = jax.tree_util.tree_map(jnp.asarray, state)
            print(f"[train] restored checkpoint at step {start_step}")
    if state is None:
        state = init_state(jax.random.PRNGKey(0), init_fn, opt)

    # preemption: final sync checkpoint on SIGTERM/SIGINT
    def _on_term(signum, frame):
        if cm is not None:
            s = int(state["step"])
            print(f"[train] preempted; checkpointing step {s}", flush=True)
            cm.wait()
            cm.save(s, state)
        sys.exit(143)

    signal.signal(signal.SIGTERM, _on_term)

    it = prefetch(batches)
    t0 = time.time()
    for i in range(start_step, args.steps):
        batch = next(it)
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        if args.fail_at_step is not None and i == args.fail_at_step:
            raise RuntimeError(f"injected failure at step {i}")
        state, metrics = step_jit(state, batch)
        if i % args.log_every == 0 or i == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            rate = (i - start_step + 1) / (time.time() - t0)
            print(f"[train] step {i} " +
                  " ".join(f"{k}={v:.4g}" for k, v in sorted(m.items()))
                  + f" ({rate:.1f} it/s)", flush=True)
        if cm is not None and i > start_step and i % args.ckpt_every == 0:
            cm.save_async(i, state)
    if cm is not None:
        cm.wait()
        cm.save(args.steps, state)
        print(f"[train] final checkpoint at step {args.steps}")


if __name__ == "__main__":
    main()
