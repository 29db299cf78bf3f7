"""gpt2_train — the NLP workload entry point (BASELINE config #4).

Reference: ``CommEfficient/gpt2_train.py`` ~L140-360 (SURVEY.md §2
"gpt2_train entry", §3.2): PersonaChat build + tokenize, special-token
vocab resize, federated training of ``GPT2DoubleHeadsModel`` with the twin
``lm_coef*CE_lm + mc_coef*CE_mc`` loss, eval reporting nll -> perplexity and
multiple-choice accuracy, and ``save_pretrained`` HF-format checkpointing.

Run-command parity examples:

  python -m commefficient_tpu.train.gpt2_train --mode sketch --k 50000 \
      --num_rows 5 --num_cols 5000000 --virtual_momentum 0.9 \
      --error_type virtual --compute_dtype bfloat16 \
      --num_workers 8 --num_devices 8                        # BASELINE #4
      # bfloat16: full-bf16 residual stream — accuracy parity, identical
      # loss trajectories; speed-neutral at single-chip microbatches
      # where the 124M-dim sketch dominates (CHANGELOG_r3 corrected
      # measurement)
  python -m commefficient_tpu.train.gpt2_train --model gpt2_tiny \
      --num_epochs 2 --num_workers 2 --num_devices 1         # CPU smoke

  python -m commefficient_tpu.train.gpt2_train --mode powersgd \
      --powersgd_rank 4 --error_type virtual --virtual_momentum 0.9 \
      # PowerSGD (PR 2): D=124M matricizes ~[11.2k, 11.2k]; the rank-4
      # factored downlink is ~89k floats (~1390x vs the dense delta) and
      # the warm-start Q rides in FedState (README mode table)

  python -m commefficient_tpu.train.gpt2_train --mode sketch --k 50000 \
      --num_rows 5 --num_cols 5000000 --virtual_momentum 0.9 \
      --error_type virtual --sketch_backend pallas            # Pallas kernels
      # sketch_backend=pallas: the CountSketch matmul path runs as tiled
      # Pallas TPU kernels (ops/pallas/) — hashes/signs/one-hots generated
      # in-kernel, targeting the r5 GPT-2 sketch-round gap; also lifts
      # --hash_family poly4 (the 4-universal guarantee class) to D=124M.
      # Identical tables/estimates to the default einsum backend up to
      # fp32 rounding (checkpoints are backend-portable).

Failure handling (resilience/; README "Failure handling & recovery"):
long GPT-2 runs are exactly where self-healing pays — ``--recover_policy
retry|demote|skip_clients`` rolls a divergence back to the last in-memory
snapshot instead of dying (``demote`` composes with the control/ ladder:
the run degrades one rung cheaper through the AOT-prewarmed switch, zero
retraces), and ``--preempt_signals true`` turns a TPU preemption's
SIGTERM into a drain + forced checkpoint + exit code 75; ``--resume``
then reproduces the uninterrupted run bit-exactly.

Sketch sizing at GPT-2 scale: keep ``num_cols >= D/25`` (~5M for
GPT-2-small, ~5x upload compression — the reference's own GPT-2 run
compresses ~3.9x uplink). The r3 lab measured d/c >= 50 DIVERGING under
virtual-error feedback for every sketch layout including a textbook
scatter sketch (CHANGELOG_r3.md); FederatedSession warns if a config is
outside the envelope. Use ``--offload_client_state true`` for
local-error/local-momentum configs — per-client state stays in host RAM
(SURVEY.md §7 hard-parts).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from commefficient_tpu.data import FedSampler, load_fed_personachat
from commefficient_tpu.models import (
    GPT2Config,
    GPT2DoubleHeads,
    gpt2_double_heads_loss,
    gpt2_tiny_config,
)
from commefficient_tpu.models.hf_gpt2 import load_hf_gpt2_params, save_pretrained
from commefficient_tpu.parallel import FederatedSession, mask_gpt2
from commefficient_tpu.utils import (
    Config,
    MetricsWriter,
    TableLogger,
    parse_args,
)
from commefficient_tpu.utils.logging import make_logdir


def build_model_and_data(cfg: Config):
    """PersonaChat + GPT-2 with the special-token vocab resize."""
    # gpt2 (small, paper scale) keeps the real GPT-2 vocab even on synthetic
    # data so D ~= 124M; gpt2_tiny is the CPU-testable config.
    base_vocab = 50257 if cfg.model == "gpt2" else 512
    train, test, real, vocab = load_fed_personachat(
        cfg.dataset_dir,
        num_clients=cfg.num_clients,
        num_candidates=cfg.num_candidates,
        max_history=cfg.max_history,
        max_seq_len=cfg.max_seq_len,
        base_vocab=base_vocab,
        seed=cfg.seed,
    )
    from commefficient_tpu.models.losses import model_dtype

    mdt = model_dtype(cfg.compute_dtype)
    if cfg.model == "gpt2":
        gcfg = GPT2Config(
            vocab_size=vocab, n_positions=max(1024, cfg.max_seq_len), dtype=mdt
        )
    elif cfg.model == "gpt2_tiny":
        tiny = gpt2_tiny_config()
        gcfg = GPT2Config(
            vocab_size=vocab,
            n_positions=max(tiny.n_positions, cfg.max_seq_len),
            n_embd=tiny.n_embd,
            n_layer=tiny.n_layer,
            n_head=tiny.n_head,
            dtype=mdt,
        )
    else:
        raise ValueError(f"unknown gpt2 model {cfg.model!r} (gpt2 | gpt2_tiny)")
    model = GPT2DoubleHeads(gcfg)
    sample = {
        "input_ids": jnp.zeros((1, cfg.num_candidates, cfg.max_seq_len), jnp.int32),
        "token_type_ids": jnp.zeros((1, cfg.num_candidates, cfg.max_seq_len), jnp.int32),
        "mc_token_ids": jnp.zeros((1, cfg.num_candidates), jnp.int32),
    }
    params = model.init(
        jax.random.key(cfg.seed),
        sample["input_ids"],
        token_type_ids=sample["token_type_ids"],
        mc_token_ids=sample["mc_token_ids"],
    )
    params, loaded = load_hf_gpt2_params(cfg.model_checkpoint, gcfg, params, seed=cfg.seed)
    loss_fn = gpt2_double_heads_loss(model.apply, cfg.lm_coef, cfg.mc_coef, compute_dtype=cfg.compute_dtype)
    return train, test, real, loaded, gcfg, model, params, loss_fn


class _Gpt2Hooks:
    """The NLP workload's plug-ins for the shared runner (train/runner.py):
    lm/mc loss accumulation, the nll->ppl eval, the legacy console row,
    and the per-epoch sample generation. See runner.WorkloadHooks."""

    def __init__(self, cfg, session, test_ds, eval_batch_size, gcfg):
        self.cfg = cfg
        self.session = session
        self.test_ds = test_ds
        self.eval_batch_size = eval_batch_size
        self.gcfg = gcfg

    def new_accumulator(self):
        return {"loss": 0.0, "lm": 0.0, "mc": 0.0}

    def accumulate(self, acc, loss, metrics):
        W = self.cfg.num_workers
        acc["loss"] += loss
        # lm/mc aux are psum'd sums of per-client means -> / W
        acc["lm"] += float(metrics.get("lm_loss", 0.0)) / W
        acc["mc"] += float(metrics.get("mc_loss", 0.0)) / W

    def evaluate(self):
        return evaluate_ppl(self.session, self.test_ds, self.eval_batch_size)

    def epoch_row(self, *, epoch, lr, acc, val, train_time, val_time,
                  steps_per_epoch):
        return {
            "epoch": epoch + 1,
            "lr": lr,
            "train_loss": acc["loss"] / steps_per_epoch,
            "train_lm": acc["lm"] / steps_per_epoch,
            "train_mc": acc["mc"] / steps_per_epoch,
            "val_nll": val["nll"],
            "val_ppl": val["ppl"],
            "val_mc_acc": val["mc_accuracy"],
            "train_time": train_time,
            "val_time": val_time,
        }

    def write_val(self, writer, val, step):
        writer.scalar("val/nll", val["nll"], step)
        writer.scalar("val/ppl", val["ppl"], step)
        writer.scalar("val/mc_acc", val["mc_accuracy"], step)

    def on_epoch_end(self, epoch, val):
        if self.gcfg is None:
            return
        # periodic generation (reference gpt2_train eval ~L280-360)
        from commefficient_tpu.data.personachat import SPECIAL_TOKENS

        prompt, gen = sample_generation(
            self.session, self.gcfg, self.test_ds,
            base_vocab=self.gcfg.vocab_size - len(SPECIAL_TOKENS),
        )
        print(f"  sample (epoch {epoch + 1}): ...{prompt[-8:].tolist()} "
              f"-> {gen.tolist()}")


def train_loop(cfg: Config, session: FederatedSession, sampler: FedSampler,
               test_ds, writer: Optional[MetricsWriter] = None,
               table: Optional[TableLogger] = None, eval_batch_size: int = 8,
               checkpointer=None, gcfg=None):
    """Epoch loop with the reference's eval: nll -> ppl + MC accuracy
    (gpt2_train.py ~L280-360). A thin adapter over the shared runner
    (train/runner.py — same scaffold and host loop as cv_train); honors
    checkpoint_every/resume like cv_train.train_loop."""
    from commefficient_tpu.train.runner import run_train_loop

    return run_train_loop(
        cfg, session, sampler,
        _Gpt2Hooks(cfg, session, test_ds, eval_batch_size, gcfg),
        writer=writer, table=table, checkpointer=checkpointer,
        generated_by="train/gpt2_train",
    )


def sample_generation(session: FederatedSession, gcfg, test_ds, base_vocab: int,
                      max_new: int = 24):
    """Decode a continuation of a held-out dialog — the reference's periodic
    generation during training (gpt2_train.py eval loop ~L280-360). The
    prompt is the gold candidate truncated at its reply start; the decode
    runs with the <speaker2> token type and stops at <eos>. Returns
    (prompt_ids, generated_ids) as numpy int arrays (token ids — decoding
    to text needs the real tokenizer, which only exists when real
    PersonaChat data is on disk)."""
    from commefficient_tpu.data.personachat import special_ids
    from commefficient_tpu.models.generate import generate
    from commefficient_tpu.models.losses import IGNORE_INDEX

    sp = special_ids(base_vocab)
    b = next(iter(test_ds.eval_batches(1)))
    mc = int(np.asarray(b["mc_labels"])[0])
    row = np.asarray(b["input_ids"])[0, mc]
    lab = np.asarray(b["lm_labels"])[0, mc]
    tt = np.asarray(b["token_type_ids"])[0, mc]
    nonmasked = np.nonzero(lab != IGNORE_INDEX)[0]
    cut = int(nonmasked[0]) if len(nonmasked) else row.shape[0] // 2
    # keep prompt + continuation inside n_positions: left-trim the prompt
    # if a long dialog leaves no headroom (the dialog builder left-
    # truncates too, so dropping the oldest context is consistent)
    trim = max(0, cut + max_new - gcfg.n_positions)
    prompt_ids, prompt_tt = row[trim:cut], tt[trim:cut]
    out = generate(
        gcfg,
        session.params,
        jnp.asarray(prompt_ids[None].astype(np.int32)),
        max_new,
        token_type_ids=jnp.asarray(prompt_tt[None].astype(np.int32)),
        new_token_type=sp["<speaker2>"],
        eos_token_id=sp["<eos>"],
    )
    return prompt_ids, np.asarray(out)[0, len(prompt_ids):]


def evaluate_ppl(session: FederatedSession, test_ds, batch_size: int):
    """nll (masked-token mean LM loss) -> ppl, plus MC accuracy — the
    reference's eval metrics (gpt2_train.py ~L280-360).

    nll is TOKEN-weighted: total masked-token NLL / total masked tokens
    (the reference computes nll over tokens). Weighting per-batch lm_loss
    means by batch rows biases ppl whenever the final batch is ragged
    (VERDICT r2 item 6); the row-weighted value is kept as a fallback for
    custom loss_fns that don't expose the sum/count pair."""
    out = session.evaluate(test_ds.eval_batches(batch_size))
    if out.get("token_count", 0.0) > 0:
        nll = out["lm_loss_sum"] / out["token_count"]
    else:
        nll = out.get("lm_loss", out["loss"])
    return {
        "nll": nll,
        "ppl": float(np.exp(min(nll, 20.0))),
        "mc_accuracy": out.get("accuracy", float("nan")),
        "loss": out["loss"],
    }


def main(argv=None, **overrides):
    from commefficient_tpu import native
    from commefficient_tpu.multihost import initialize_multihost
    from commefficient_tpu.parallel.mesh import initialize_distributed
    from commefficient_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    cfg = parse_args(
        argv,
        defaults=dict(
            model="gpt2",
            dataset_name="personachat",
            local_batch_size=4,
            lr_scale=0.16,  # reference gpt2 lr territory (paper appendix)
            max_grad_norm=1.0,
        ),
        **overrides,
    )
    # --distributed: the checked multihost bring-up (names a missing
    # coordinator or a process-count/num_hosts mismatch); otherwise the
    # legacy env-driven path (no-op single-host)
    if not initialize_multihost(cfg):
        initialize_distributed()
    train, test, real, hf_loaded, gcfg, model, params, loss_fn = (
        build_model_and_data(cfg)
    )
    print(
        f"dataset=personachat (real={real}) model={cfg.model} "
        f"(V={gcfg.vocab_size}, L={gcfg.n_layer}, E={gcfg.n_embd}, "
        f"hf_weights={hf_loaded}) mode={cfg.mode} "
        f"clients={train.num_clients} workers={cfg.num_workers} "
        f"host_loader={native.describe()}"
    )
    if not real:
        print("WARNING: personachat json not found — synthetic stand-in "
              "(pipeline-correct; metrics are not paper numbers)")
    if cfg.model_axis > 1 or cfg.seq_axis > 1:
        # model/seq mesh axes (VERDICT r2 item 3): per-client loss compute
        # shards heads over `model` and tokens (ring attention) over `seq`
        # inside the round's shard_map; params/compression stay the
        # replicated flat vector. Eval is ALSO sharded over model/seq
        # (VERDICT r3 missing 5: a model that needs the model axis to fit
        # must be able to validate), via tensor.build_tp_eval_fn.
        from commefficient_tpu.ops.param_utils import ravel_params
        from commefficient_tpu.parallel.mesh import make_mesh
        from commefficient_tpu.parallel.tensor import (
            build_tp_eval_fn,
            build_tp_flat_loss,
        )

        mesh = make_mesh(cfg.num_devices, cfg.model_axis, cfg.seq_axis)
        print(f"mesh: workers={cfg.num_devices} x model={cfg.model_axis} "
              f"x seq={cfg.seq_axis}")
        session = FederatedSession(
            cfg,
            params,
            build_tp_flat_loss(gcfg, mesh, cfg.lm_coef, cfg.mc_coef,
                               compute_dtype=cfg.compute_dtype),
            mesh=mesh,
            eval_fn=build_tp_eval_fn(
                gcfg, mesh, ravel_params(params)[1], cfg.lm_coef,
                cfg.mc_coef, compute_dtype=cfg.compute_dtype,
            ),
            mask_batch=mask_gpt2,
        )
    else:
        session = FederatedSession(cfg, params, loss_fn, mask_batch=mask_gpt2)
    bpr = session.bytes_per_round()
    print(f"grad_size D={session.grad_size}  upload/client/round="
          f"{bpr['upload_bytes']:,} B  download={bpr['download_bytes']:,} B")
    sampler = FedSampler(
        train,
        num_workers=cfg.num_workers,
        local_batch_size=cfg.sampler_batch_size,
        seed=cfg.seed,
    )
    # token arrays live in HBM when they fit; rounds ship only [W, B] indices
    session.maybe_attach_data(train, sampler)
    from commefficient_tpu.control import controller_header

    writer = MetricsWriter(make_logdir(cfg), cfg.tensorboard, cfg=cfg,
                           extra_header=controller_header(session))
    from commefficient_tpu.utils.checkpoint import FedCheckpointer

    # full-state checkpoints go under <checkpoint_dir>/state; the HF-format
    # save_pretrained export (below) stays at the top level.
    checkpointer = FedCheckpointer(
        cfg.replace(checkpoint_dir=os.path.join(cfg.checkpoint_dir, "state"))
        if cfg.checkpoint_dir
        else cfg
    )
    from commefficient_tpu.resilience import EXIT_PREEMPTED, PreemptShutdown

    try:
        # the shared runner owns the end-of-training force-save and the
        # crash-path checkpointer close (the close below is idempotent)
        val = train_loop(cfg, session, sampler, test, writer,
                         checkpointer=checkpointer, gcfg=gcfg)
    except PreemptShutdown as e:
        # preemption-safe shutdown (resilience/): drained + force-saved by
        # the runner; the distinct exit code tells orchestrators to retry
        # with --resume (the HF-format export below is skipped — the run
        # is not finished)
        print(str(e))
        raise SystemExit(EXIT_PREEMPTED) from e
    finally:
        checkpointer.close()
        writer.close()
    print(f"final: val_nll={val['nll']:.4f} ppl={val['ppl']:.2f} "
          f"mc_acc={val['mc_accuracy']:.4f}")
    if cfg.checkpoint_dir:
        save_pretrained(cfg.checkpoint_dir, gcfg, session.params)
        print(f"saved HF-format checkpoint to {cfg.checkpoint_dir}")
    return val


if __name__ == "__main__":
    main()
