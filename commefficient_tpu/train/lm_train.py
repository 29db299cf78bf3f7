"""lm_train — language modelling on a packed federated text set.

The third workload entry, beside ``cv_train`` and ``gpt2_train``, over the
same runner, session and sampler: a decoder-only LM (``--model laguna_xs2``:
one chip's share of Laguna-XS.2, ``models/laguna.py``; ``keye_vl2``: one
chip's share of Keye-VL-2.0-30B-A3B's language model, ``models/keye.py``;
``sdar_30b_a3b``: one chip's share of SDAR-30B-A3B-Chat, ``models/sdar.py``;
``kimi_linear_48b``: one chip's share of Kimi-Linear-48B-A3B-Instruct,
``models/kimi_linear.py``; ``laguna_tiny`` / ``keye_tiny`` / ``sdar_tiny`` /
``kimi_linear_tiny`` for the CPU) trained on
``--dataset_name fedtext`` (``data/fedtext.py``: packed documents, one shard
of rows per client), eval reporting nll -> perplexity. The preset decides
the objective: next-token loss, or for a block-diffusion preset (its
``block_length`` set) the ``1 / t``-weighted loss on the positions a fresh
noise masks every round. That noise rides the round's feed
(``fedtext.BlockNoise``: the sampler's plan, applied in the graph beside the
gather); eval's is one fixed draw a test row, so its nll is a bound on the
data's, comparable from epoch to epoch.

  python -m commefficient_tpu.train.lm_train --mode uncompressed \
      --num_workers 4 --local_batch_size 2 --max_seq_len 2048   # the chip
  python -m commefficient_tpu.train.lm_train --model laguna_tiny \
      --max_seq_len 128 --num_clients 8 --num_workers 2 --num_epochs 1  # CPU

``--max_seq_len`` is a multiple of 128 (the attention kernel's lanes);
``--doc_median`` is the packed documents' median length (300).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.data import FedSampler, load_fed_text
from commefficient_tpu.data.fedtext import BlockNoise
from commefficient_tpu.models import block_diffusion_lm_loss, causal_lm_loss
from commefficient_tpu.models import keye, kimi_linear, laguna, sdar
from commefficient_tpu.models.laguna import LagunaLM
from commefficient_tpu.models.losses import IGNORE_INDEX, model_dtype
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.utils import Config, MetricsWriter, TableLogger, parse_args
from commefficient_tpu.utils.logging import make_logdir

PRESETS = {**laguna.PRESETS, **keye.PRESETS, **sdar.PRESETS, **kimi_linear.PRESETS}
DEFAULTS = dict(model="laguna_xs2", dataset_name="fedtext", num_clients=64,
                local_batch_size=2, max_seq_len=2048, max_grad_norm=1.0, lr_scale=0.01)


def mask_lm(batch, row_mask):
    """Eval's padded tail rows carry no label."""
    return {**batch, "lm_labels": jnp.where(row_mask[:, None], batch["lm_labels"],
                                            IGNORE_INDEX)}


def round_augment(lcfg):
    """What the preset's objective adds to a round's feed (none: nothing)."""
    return BlockNoise(lcfg.block_length) if lcfg.block_length else None


def build_model_and_data(cfg: Config):
    """``(train, test, lcfg, model, params, loss_fn)``."""
    if cfg.model not in PRESETS:
        raise ValueError(f"unknown lm model {cfg.model!r} ({' | '.join(PRESETS)})")
    if cfg.dataset_name != "fedtext":
        raise ValueError(f"unknown lm dataset {cfg.dataset_name!r} (fedtext)")
    lcfg = PRESETS[cfg.model](dtype=model_dtype(cfg.compute_dtype))
    noise = round_augment(lcfg)
    # a block-diffusion preset's [MASK] (the id under <eos>) is in no document
    train, test = load_fed_text(num_clients=cfg.num_clients, seq_len=cfg.max_seq_len,
                                vocab=lcfg.vocab_held, seed=cfg.seed, doc_median=cfg.doc_median,
                                reserved=1 if noise else 0)
    if noise:   # eval's noise is drawn once, a test row its own
        test.data.update(noise.apply(test.data, *noise.fixed(len(test), cfg.max_seq_len,
                                                             cfg.seed)))
    model = LagunaLM(lcfg)
    # shapes only: the real init would run every kernel once on zeros
    shapes = jax.eval_shape(model.init, jax.random.key(cfg.seed),
                            jnp.zeros((1, cfg.max_seq_len), jnp.int32))
    params = _init_params(shapes, cfg.seed, lcfg.initializer_range)
    make_loss = block_diffusion_lm_loss if noise else causal_lm_loss
    return train, test, lcfg, model, params, make_loss(
        model.apply, compute_dtype=cfg.compute_dtype)


def _init_params(shapes, seed: int, std: float):
    """Normal(0, std) for every matrix, ones for every ``scale``: what the
    modules' own initializers draw, without tracing the model: one draw of
    all D numbers, cut into the leaves (a draw a leaf compiles for a minute
    on the chip)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    sizes = [int(np.prod(a.shape)) for _, a in leaves]
    starts = np.cumsum([0] + sizes)

    def draw(key):
        flat = std * jax.random.normal(key, (int(starts[-1]),), jnp.float32)
        return [jnp.ones(a.shape, a.dtype) if getattr(path[-1], "key", "") == "scale"
                else flat[at:at + n].reshape(a.shape).astype(a.dtype)
                for (path, a), at, n in zip(leaves, starts, sizes)]

    return jax.tree.unflatten(treedef, jax.jit(draw)(jax.random.key(seed)))


class _LmHooks:
    """The LM workload's plug-ins for the shared runner (runner.WorkloadHooks)."""

    def __init__(self, cfg, session, test_ds, eval_batch_size):
        self.cfg, self.session = cfg, session
        self.test_ds, self.eval_batch_size = test_ds, eval_batch_size

    def new_accumulator(self):
        return {"loss": 0.0, "held": 0.0, "dropped": 0.0, "selected": 0.0, "causal": 0.0,
                "ties": 0.0, "masked": 0.0, "labelled": 0.0, "kept": 0.0, "slots": 0.0}

    def accumulate(self, acc, loss, metrics):
        acc["loss"] += loss
        acc["held"] += float(metrics.get("moe/held_assignments", 0.0))
        acc["dropped"] += float(metrics.get("moe/dropped", 0.0))
        # an indexed-attention model's counters (absent otherwise)
        acc["selected"] += float(metrics.get("attn/selected_pairs", 0.0))
        acc["causal"] += float(metrics.get("attn/causal_pairs", 0.0))
        acc["ties"] += float(metrics.get("attn/select_ties", 0.0))
        # a block-diffusion model's (absent otherwise)
        acc["masked"] += float(metrics.get("diffusion/masked_tokens", 0.0))
        acc["labelled"] += float(metrics.get("diffusion/labelled_tokens", 0.0))
        # a recurrent model's (absent otherwise)
        acc["kept"] += float(metrics.get("kda/state_kept", 0.0))
        acc["slots"] += float(metrics.get("kda/state_slots", 0.0))

    def evaluate(self):
        return evaluate_ppl(self.session, self.test_ds, self.eval_batch_size)

    def epoch_row(self, *, epoch, lr, acc, val, train_time, val_time, steps_per_epoch):
        row = {
            "epoch": epoch + 1, "lr": lr,
            "train_loss": acc["loss"] / steps_per_epoch,
            "held_per_round": acc["held"] / steps_per_epoch,
            "dropped": acc["dropped"],
        }
        if acc["causal"]:
            # attn/selected_share: the pairs attended to over the causal pairs
            row.update(selected_share=acc["selected"] / acc["causal"], select_ties=acc["ties"])
        if acc["labelled"]:
            # the labelled tokens the rounds' noise masked: the mean t, 0.5 under U[1e-3, 1]
            row.update(masked_share=acc["masked"] / acc["labelled"])
        if acc["slots"]:
            # the mean share of a key channel's state that survives a chunk's decay
            row.update(kda_state_kept_share=acc["kept"] / acc["slots"])
        return {**row, "val_nll": val["nll"], "val_ppl": val["ppl"],
                "train_time": train_time, "val_time": val_time}

    def write_val(self, writer, val, step):
        writer.scalar("val/nll", val["nll"], step)
        writer.scalar("val/ppl", val["ppl"], step)

    def on_epoch_end(self, epoch, val):
        pass


def evaluate_ppl(session: FederatedSession, test_ds, batch_size: int):
    """Token-weighted nll over the test rows -> perplexity."""
    out = session.evaluate(test_ds.eval_batches(batch_size))
    nll = out["lm_loss_sum"] / max(out["token_count"], 1.0)
    return {"nll": nll, "ppl": float(np.exp(min(nll, 20.0))), "loss": out["loss"]}


def train_loop(cfg: Config, session: FederatedSession, sampler: FedSampler, test_ds,
               writer: Optional[MetricsWriter] = None, table: Optional[TableLogger] = None,
               eval_batch_size: int = 2, checkpointer=None):
    from commefficient_tpu.train.runner import run_train_loop

    return run_train_loop(
        cfg, session, sampler, _LmHooks(cfg, session, test_ds, eval_batch_size),
        writer=writer, table=table, checkpointer=checkpointer,
        generated_by="train/lm_train",
    )


def build_session_and_sampler(cfg: Config, train, params, loss_fn):
    session = FederatedSession(cfg, params, loss_fn, mask_batch=mask_lm)
    # the preset again, for its block length alone: the benchmark's entry calls
    # this with these four arguments (benchmark/entries/lm_train.py)
    augment = round_augment(PRESETS[cfg.model]())
    sampler = FedSampler(train, num_workers=cfg.num_workers,
                         local_batch_size=cfg.sampler_batch_size, seed=cfg.seed,
                         augment=augment)
    # the token rows live in HBM (8 MB); rounds ship only [W, B] indices
    # and, for a block-diffusion preset, the round's noise as its plan
    session.maybe_attach_data(train, sampler, augment)
    return session, sampler


def main(argv=None, **overrides):
    from commefficient_tpu import native
    from commefficient_tpu.control import controller_header
    from commefficient_tpu.multihost import initialize_multihost
    from commefficient_tpu.parallel.mesh import initialize_distributed
    from commefficient_tpu.resilience import EXIT_PREEMPTED, PreemptShutdown
    from commefficient_tpu.utils.checkpoint import FedCheckpointer
    from commefficient_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    cfg = parse_args(argv, defaults=DEFAULTS, **overrides)
    if not initialize_multihost(cfg):
        initialize_distributed()
    train, test, lcfg, _model, params, loss_fn = build_model_and_data(cfg)
    print(f"dataset=fedtext (synthetic) model={cfg.model} (V={lcfg.vocab_held} of "
          f"{lcfg.vocab_size}, L={lcfg.num_layers}, E={lcfg.hidden_size}, experts "
          f"{len(lcfg.experts_held)} of {lcfg.num_experts}) mode={cfg.mode} "
          f"clients={train.num_clients} workers={cfg.num_workers} "
          f"host_loader={native.describe()}")
    session, sampler = build_session_and_sampler(cfg, train, params, loss_fn)
    bpr = session.bytes_per_round()
    print(f"grad_size D={session.grad_size}  upload/client/round="
          f"{bpr['upload_bytes']:,} B  download={bpr['download_bytes']:,} B")
    writer = MetricsWriter(make_logdir(cfg), cfg.tensorboard, cfg=cfg,
                           extra_header=controller_header(session))
    checkpointer = FedCheckpointer(cfg)
    try:
        val = train_loop(cfg, session, sampler, test, writer, checkpointer=checkpointer)
    except PreemptShutdown as e:
        print(str(e))
        raise SystemExit(EXIT_PREEMPTED) from e
    finally:
        checkpointer.close()
        writer.close()
    print(f"final: val_nll={val['nll']:.4f} ppl={val['ppl']:.2f}")
    return val


if __name__ == "__main__":
    main()
