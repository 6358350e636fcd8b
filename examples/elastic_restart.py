"""Fault-tolerance demo: train, crash, restart — then rescale the mesh.

  1. trains 60 steps, checkpointing every 20
  2. simulates a node failure (trainer object dropped on the floor)
  3. a fresh Trainer resumes from step 60 deterministically
  4. the checkpoint is then restored onto a DIFFERENT mesh shape
     (elastic rescale path used when hosts join/leave)

Run:  PYTHONPATH=src python examples/elastic_restart.py
"""

import tempfile

import jax
import numpy as np

from repro.data import make_token_corpus, uniform_batches
from repro.models import ModelConfig, init_params
from repro.optim import Adam
from repro.train import Trainer, TrainerConfig, checkpoint as ckpt
from repro.train.elastic import rescale_plan, restore_latest_valid_on_mesh
from repro.launch.cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = ModelConfig(name="elastic-demo", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab=256,
                      chunk=16, loss_chunk=32, dtype="float32",
                      rope_theta=10000.0)
    corpus = make_token_corpus(0, 512, 32, cfg.vocab)
    key = jax.random.PRNGKey(0)

    with tempfile.TemporaryDirectory() as d:
        def fresh(resume):
            return Trainer(cfg, init_params(key, cfg), Adam(lr=1e-2),
                           uniform_batches(corpus, 8, seed=1),
                           TrainerConfig(ckpt_dir=d, ckpt_every=20,
                                         log_every=20),
                           resume=resume)

        t1 = fresh(resume=False)
        t1.run(60)
        t1.finalize()
        print(f"phase 1: trained to step {t1.step}, "
              f"latest ckpt = step {ckpt.latest_step(d)}")
        loss_before_crash = t1.metrics_history[-1]["loss"]
        del t1  # << node failure

        t2 = fresh(resume=True)
        print(f"phase 2: restarted at step {t2.step} (auto-resume)")
        t2.run(40)
        t2.finalize()
        print(f"phase 2: continued to step {t2.step}, "
              f"loss {t2.metrics_history[-1]['loss']:.4f} "
              f"(pre-crash {loss_before_crash:.4f})")

        # elastic rescale: restore the same checkpoint onto a 1-device
        # host mesh with proper shardings (on a fleet: the new pod count)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        template = {"params": init_params(key, cfg),
                    "opt_state": Adam(lr=1e-2).init(init_params(key, cfg))}
        # integrity-checked selection: a checkpoint truncated by the
        # "failure" would be skipped for the newest VALID one
        step_v, state, extra = restore_latest_valid_on_mesh(
            d, template, mesh)
        n = sum(x.size for x in jax.tree.leaves(state["params"]))
        print(f"phase 3: restored step {extra['step']} onto mesh "
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"({n/1e6:.2f}M params resharded)")
        print("rescale plan 256->512 chips:",
              rescale_plan(256, 512, global_batch=256))


if __name__ == "__main__":
    main()
