"""Training loop with checkpoint/resume: `python -m ggrmcp_tpu train`.

The reference has no training or persistence (SURVEY.md §5.4); here the
loop drives models/training.py's sharded train step over the device
mesh and persists through serving/checkpoint.py (Orbax):

    <checkpoint_dir>/step_N/state   full TrainState — resume target
    <checkpoint_dir>/step_N/params  weights only — what a serving
                                    sidecar points serving.checkpoint_path at

Data is either a raw text file (byte-tokenized, chunked to seq_len,
cycled) or a deterministic synthetic token stream — enough to exercise
fine-tuning end-to-end and to produce real checkpoints for serving.
"""

from __future__ import annotations

import logging
import os
import re
import time
from functools import partial
from typing import Iterator, Optional

import numpy as np

from ggrmcp_tpu.core.config import TrainingConfig

logger = logging.getLogger("ggrmcp.models.trainer")


def _data_stream(
    cfg: TrainingConfig, vocab_size: int, start_step: int = 0
) -> Iterator[np.ndarray]:
    """Yields [batch, seq_len] int32 batches forever. `start_step` is
    folded into the rng seed so a resumed run does not re-train on the
    batches the pre-crash run already consumed."""
    rng = np.random.default_rng([cfg.seed, start_step])
    if cfg.data_path:
        from ggrmcp_tpu.serving.tokenizer import ByteTokenizer

        with open(cfg.data_path, "r", encoding="utf-8") as fh:
            ids = ByteTokenizer().encode(fh.read())
        if len(ids) < cfg.seq_len + 1:
            raise ValueError(
                f"data file too small: {len(ids)} tokens < seq_len+1"
            )
        tokens = np.asarray(ids, np.int32) % vocab_size
        while True:
            starts = rng.integers(
                0, len(tokens) - cfg.seq_len, size=cfg.batch_size
            )
            yield np.stack([tokens[s : s + cfg.seq_len] for s in starts])
    else:
        while True:
            yield rng.integers(
                0, vocab_size, size=(cfg.batch_size, cfg.seq_len),
                dtype=np.int32,
            )


def latest_step(checkpoint_dir: str) -> Optional[int]:
    """Highest N with a step_N/state checkpoint under the dir."""
    if not checkpoint_dir or not os.path.isdir(checkpoint_dir):
        return None
    steps = [
        int(m.group(1))
        for name in os.listdir(checkpoint_dir)
        if (m := re.fullmatch(r"step_(\d+)", name))
        and os.path.isdir(os.path.join(checkpoint_dir, name, "state"))
    ]
    return max(steps) if steps else None


def train(cfg: TrainingConfig) -> "TrainState":  # noqa: F821
    """Run the loop; returns the final (host-fetched) TrainState."""
    from ggrmcp_tpu.utils.jaxenv import init_runtime

    init_runtime("trainer")
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from ggrmcp_tpu import models as models_mod
    from ggrmcp_tpu.models import training
    from ggrmcp_tpu.parallel import mesh as mesh_mod
    from ggrmcp_tpu.serving import checkpoint

    family, model_cfg = models_mod.get_model(cfg.model)
    if family == "bert":
        raise ValueError("training targets decoder models")
    fam = models_mod.family_module(model_cfg)
    mesh = mesh_mod.build_mesh(cfg.mesh)
    optimizer = training.make_optimizer(cfg.learning_rate, cfg.weight_decay)

    start_step = 0
    resume_from = latest_step(cfg.checkpoint_dir) if cfg.resume else None
    if resume_from is not None:
        like = jax.eval_shape(
            partial(training.init_train_state, cfg=model_cfg,
                    optimizer=optimizer),
            jax.random.PRNGKey(cfg.seed),
        )
        # Dict container on disk and in the restore target: the concrete
        # optax state structure comes from `like`, the outer dict keeps
        # save/restore structurally symmetric.
        restored = checkpoint.restore(
            os.path.join(cfg.checkpoint_dir, f"step_{resume_from}", "state"),
            like={"params": like.params, "opt_state": like.opt_state,
                  "step": like.step},
        )
        state = training.TrainState(
            restored["params"], restored["opt_state"], restored["step"]
        )
        start_step = int(state.step)
        logger.info("resumed from step %d", start_step)
    else:
        state = training.init_train_state(
            jax.random.PRNGKey(cfg.seed), model_cfg, optimizer
        )

    # Place params on the mesh with the family's TP/DP specs (axes that
    # don't divide the actual dims are dropped), opt state alongside.
    specs = fam.param_specs(model_cfg)
    params = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(
            x, NamedSharding(
                mesh, mesh_mod.compatible_spec(s, np.shape(x), mesh)
            )
        ),
        state.params, specs,
    )
    state = training.TrainState(
        params, jax.device_put(state.opt_state),
        jnp.asarray(state.step, jnp.int32),
    )
    step_fn, _ = training.make_sharded_train_step(model_cfg, mesh, optimizer)

    data = _data_stream(cfg, model_cfg.vocab_size, start_step)
    t0 = time.monotonic()
    with mesh:
        for step in range(start_step, cfg.steps):
            batch = jnp.asarray(next(data))
            state, loss = step_fn(state, batch)
            if (step + 1) % cfg.log_every_steps == 0 or step + 1 == cfg.steps:
                loss_f = float(loss)
                rate = (step + 1 - start_step) / (time.monotonic() - t0)
                logger.info(
                    "step %d/%d loss=%.4f (%.2f steps/s)",
                    step + 1, cfg.steps, loss_f, rate,
                )
                if not np.isfinite(loss_f):
                    raise FloatingPointError(
                        f"non-finite loss at step {step + 1}"
                    )
            done = step + 1
            if cfg.checkpoint_dir and (
                done % cfg.save_every_steps == 0 or done == cfg.steps
            ):
                _save(cfg.checkpoint_dir, done, state, checkpoint)
    return state


def _save(root: str, step: int, state, checkpoint) -> None:
    base = os.path.join(root, f"step_{step}")
    checkpoint.save(os.path.join(base, "params"), state.params)
    checkpoint.save(
        os.path.join(base, "state"),
        {"params": state.params, "opt_state": state.opt_state,
         "step": state.step},
    )
    logger.info("checkpointed step %d to %s", step, base)
