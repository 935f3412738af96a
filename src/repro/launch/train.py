"""End-to-end training driver.

Wires every substrate together: synthetic corpus -> DFA block-list filter
(the paper's engine as a pipeline stage) -> packed batches -> sharded
jit train step -> async checkpoints -> restart-on-failure.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
      --steps 200 --batch 8 --seq 256

``--smoke`` shrinks the config for CPU; drop it on a real pod and pass
--mesh-data/--mesh-model for the production layout.
"""

from __future__ import annotations

import argparse
import itertools
import time

import numpy as np

import jax

from ..configs import get_config, reduce_for_smoke
from jax import set_mesh
from ..data import CorpusConfig, CorpusFilter, LoaderConfig, data_stream, generate_documents
from ..distributed import sharding as shr
from ..training import AdamWConfig, CheckpointManager, TrainOptions
from ..training.train_loop import (init_train_state_sharded, jit_train_step,
                                   make_train_step, init_train_state)
from ..distributed.fault_tolerance import RestartManager
from ..launch.mesh import make_local_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--filter-patterns", nargs="*", default=[r"SECRET-[0-9]+"])
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    mesh = make_local_mesh(args.mesh_data, args.mesh_model)

    # data: filtered + packed
    corpus = CorpusConfig(n_documents=10_000, doc_len=args.seq * 4, seed=1)
    filt = CorpusFilter(args.filter_patterns, num_chunks=8)
    stream = data_stream(generate_documents(corpus),
                         LoaderConfig(batch_size=args.batch, seq_len=args.seq),
                         corpus_filter=filt)
    batches = ({"tokens": b["tokens"] % cfg.vocab_size,
                "labels": b["labels"] % cfg.vocab_size} for b in stream)

    opts = TrainOptions(
        num_microbatches=args.microbatches,
        optimizer=AdamWConfig(lr=args.lr, warmup_steps=20,
                              total_steps=args.steps))
    with set_mesh(mesh):
        state = init_train_state_sharded(cfg, jax.random.PRNGKey(0), mesh, opts)
        first = next(batches)
        bspecs = shr.batch_specs(first, mesh, args.batch)
        step_fn = jit_train_step(cfg, mesh, state, bspecs, opts)

        mgr = CheckpointManager(args.ckpt_dir, keep=2)
        start = 0
        if args.resume:
            like = jax.tree.map(np.asarray, state)
            from ..training.train_loop import state_shardings
            state, start = mgr.restore(like, state_shardings(state, mesh))
            print(f"resumed from step {start}")

        it = itertools.chain([first], batches)

        def one_step(st, i):
            batch = next(it)
            st, metrics = step_fn(st, batch)
            if i % 10 == 0:
                print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e}")
            return st

        rm = RestartManager(
            save_fn=mgr.save,
            restore_fn=lambda: mgr.restore(jax.tree.map(np.asarray, state)))
        t0 = time.time()
        state, at = rm.run(state, start, args.steps, one_step,
                           checkpoint_every=args.ckpt_every)
        mgr.save(state, at)
        mgr.wait()
    dt = time.time() - t0
    print(f"done: {at} steps in {dt:.1f}s "
          f"({args.batch * args.seq * (at - start) / max(dt, 1e-9):.0f} tok/s); "
          f"filter dropped {filt.stats.dropped}/{filt.stats.scanned} docs, "
          f"model-speedup {filt.stats.model_speedup:.2f}x")


if __name__ == "__main__":
    main()
