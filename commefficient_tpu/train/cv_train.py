"""cv_train — the CV workload entry point.

Reference: ``CommEfficient/cv_train.py`` ~L30-240 (SURVEY.md §2 "cv_train
entry", §3.1): CLI -> federated dataset + sampler -> FedModel/FedOptimizer
-> epoch loop with the piecewise-linear LR (0 -> lr_scale @ pivot_epoch ->
0), per-epoch validation, console table + metrics logging.

Run-command parity examples:

  python -m commefficient_tpu.train.cv_train --mode uncompressed \
      --num_workers 1 --num_devices 1 --num_epochs 2          # BASELINE #1
  python -m commefficient_tpu.train.cv_train --mode sketch --k 50000 \
      --num_rows 5 --num_cols 500000 --virtual_momentum 0.9 \
      --error_type virtual --num_workers 8 --num_devices 8    # BASELINE #2
  python -m commefficient_tpu.train.cv_train --dataset_name femnist \
      --mode local_topk --error_type local --num_clients 100  # BASELINE #3
  python -m commefficient_tpu.train.cv_train --mode powersgd \
      --powersgd_rank 4 --error_type virtual --virtual_momentum 0.9 \
      --num_workers 8 --num_devices 8        # PowerSGD low-rank (PR 2):
      # rank-4 warm-started power iteration, ~320x downlink compression
      # at ResNet-9 scale (see README mode table / compress/powersgd.py)

Failure handling (resilience/; README "Failure handling & recovery"):
``--recover_policy retry|demote|skip_clients`` turns a chaos- or
hardware-induced divergence into a bounded rollback-and-recover instead
of a dead run (``--snapshot_every`` sets the rollback granularity,
``--max_recoveries`` the give-up bound; needs ``--telemetry_level >= 1``);
``--preempt_signals true`` (or the seeded chaos event ``preempt@R``)
makes SIGTERM/SIGINT a drain + forced checkpoint + exit code 75 instead
of lost rounds — rerun with ``--resume`` to continue bit-exactly.

Sketch kernels: ``--sketch_backend pallas`` runs the CountSketch matmul
path as tiled Pallas TPU kernels (ops/pallas/ — in-kernel hashes/signs,
fused overlap-add; same tables as the default einsum backend to fp32
rounding). ``--hash_family poly4`` under the pallas backend works at any
scale whose PADDED layout stays under 2^31 - 1 — GPT-2-small's D=124M
included; beyond ~1.4e9 params the kernel raises a clear ValueError (the
4-universal family lives in GF(2^31-1)). The einsum path materializes a
host-side [d_eff] sign vector and is CV-scale-only for poly4.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from commefficient_tpu.data import (
    FedSampler,
    augment_batch,
    load_fed_cifar10,
    load_fed_cifar100,
    load_fed_emnist,
    load_fed_imagenet,
)
from commefficient_tpu.models import ResNet9, classification_loss, fixup_resnet50
from commefficient_tpu.parallel import FederatedSession
from commefficient_tpu.utils import (
    Config,
    MetricsWriter,
    TableLogger,
    parse_args,
)
from commefficient_tpu.utils.logging import make_logdir


def build_model_and_data(cfg: Config):
    """Dataset + model for cfg.dataset_name / cfg.model.

    Image batches stay uint8 on the host (loaders no longer normalize);
    ``prep`` normalizes ON DEVICE inside the loss — shipping uint8
    quarters the per-round host->TPU transfer.
    """
    from commefficient_tpu.data.cifar import CIFAR10_MEAN, CIFAR10_STD, device_normalizer
    from commefficient_tpu.data.imagenet import IMAGENET_MEAN, IMAGENET_STD

    prep = None
    if cfg.dataset_name == "cifar10":
        train, test, real = load_fed_cifar10(
            cfg.dataset_dir, num_clients=cfg.num_clients, iid=cfg.iid,
            seed=cfg.seed, synthetic_variant=cfg.synthetic_variant,
        )
        sample_shape = (1, 32, 32, 3)
        num_classes = cfg.resolved_num_classes
        augment = augment_batch
        prep = device_normalizer(CIFAR10_MEAN, CIFAR10_STD)
    elif cfg.dataset_name == "cifar100":
        train, test, real = load_fed_cifar100(
            cfg.dataset_dir, num_clients=cfg.num_clients, iid=cfg.iid, seed=cfg.seed
        )
        sample_shape = (1, 32, 32, 3)
        num_classes = cfg.resolved_num_classes
        augment = augment_batch
        prep = device_normalizer(CIFAR10_MEAN, CIFAR10_STD)
    elif cfg.dataset_name == "femnist":
        train, test, real = load_fed_emnist(
            cfg.dataset_dir, num_clients=cfg.num_clients, seed=cfg.seed,
            label_noise=cfg.label_noise,
        )
        sample_shape = (1, 28, 28, 1)
        num_classes = 62
        augment = None
    elif cfg.dataset_name == "imagenet":
        # num_classes must reach the loader too: the synthetic fallback
        # otherwise fabricates 1000-class labels against a smaller head
        # (out-of-range gather in the CE under jit)
        train, test, real = load_fed_imagenet(
            cfg.dataset_dir, num_clients=cfg.num_clients, iid=cfg.iid,
            seed=cfg.seed, num_classes=cfg.resolved_num_classes,
        )
        sample_shape = (1,) + train.data["x"].shape[1:]
        num_classes = cfg.resolved_num_classes
        # train-time random-resized-crop + flip (the reference's
        # torchvision transform, data_utils/fed_imagenet.py ~L1-120) —
        # plan-based so the native kernel and device-resident path apply it
        from commefficient_tpu.data.imagenet import ImageNetAugment

        augment = ImageNetAugment()
        prep = device_normalizer(IMAGENET_MEAN, IMAGENET_STD)
    else:
        raise ValueError(f"unknown dataset {cfg.dataset_name!r}")

    from commefficient_tpu.models.losses import model_dtype

    mdt = model_dtype(cfg.compute_dtype)
    if cfg.model == "resnet9":
        model = ResNet9(num_classes=num_classes, dtype=mdt)
    elif cfg.model in ("fixup_resnet50", "resnet50"):
        model = fixup_resnet50(num_classes=num_classes, dtype=mdt)
    else:
        raise ValueError(f"unknown model {cfg.model!r}")
    params = model.init(jax.random.key(cfg.seed), jnp.zeros(sample_shape))
    loss_fn = classification_loss(model.apply, prep=prep, compute_dtype=cfg.compute_dtype)
    return train, test, real, model, params, loss_fn, augment


def build_session_and_sampler(cfg: Config, train, params, loss_fn, augment):
    """Session + sampler wiring shared by main() and scripts/accuracy_run.py.
    (The fedavg microbatch convention lives in Config.sampler_batch_size.)

    When the training set fits ``cfg.device_data_max_mb`` it is attached
    device-resident (session.attach_data): rounds then ship only sample
    indices + the augment plan instead of pixel batches."""
    session = FederatedSession(cfg, params, loss_fn)
    sampler = FedSampler(
        train,
        num_workers=cfg.num_workers,
        local_batch_size=cfg.sampler_batch_size,
        seed=cfg.seed,
        augment=augment,
    )
    session.maybe_attach_data(train, sampler, augment)
    return session, sampler


class _CvHooks:
    """The CV workload's plug-ins for the shared runner (train/runner.py):
    loss/accuracy accumulation, the classification eval, the legacy
    console row. See runner.WorkloadHooks for the contract."""

    def __init__(self, session, test_ds, eval_batch_size):
        self.session = session
        self.test_ds = test_ds
        self.eval_batch_size = eval_batch_size

    def new_accumulator(self):
        return {"loss": 0.0, "correct": 0.0, "count": 0.0}

    def accumulate(self, acc, loss, metrics):
        acc["loss"] += loss
        acc["correct"] += float(metrics.get("correct", 0.0))
        acc["count"] += float(metrics.get("count", 0.0))

    def evaluate(self):
        return self.session.evaluate(
            self.test_ds.eval_batches(self.eval_batch_size)
        )

    def epoch_row(self, *, epoch, lr, acc, val, train_time, val_time,
                  steps_per_epoch):
        return {
            "epoch": epoch + 1,
            "lr": lr,
            "train_loss": acc["loss"] / steps_per_epoch,
            "train_acc": acc["correct"] / max(acc["count"], 1.0),
            "val_loss": val["loss"],
            "val_acc": val.get("accuracy", float("nan")),
            "train_time": train_time,
            "val_time": val_time,
        }

    def write_val(self, writer, val, step):
        writer.scalar("val/loss", val["loss"], step)
        writer.scalar("val/acc", val.get("accuracy", 0.0), step)

    def on_epoch_end(self, epoch, val):
        pass


def train_loop(cfg: Config, session: FederatedSession, sampler: FedSampler,
               test_ds, writer: Optional[MetricsWriter] = None,
               table: Optional[TableLogger] = None, eval_batch_size: int = 512,
               checkpointer=None):
    """The epoch loop (cv_train.py ~L120-240). Returns final val metrics.

    A thin adapter over the shared runner (train/runner.py), which owns
    the deferred-drain/checkpoint/crash scaffold and the host loop; only
    the CV-specific pieces (accuracy accumulation, eval, the console row)
    live here. Checkpoint/resume semantics are the
    runner's: a resumed run fast-forwards to the checkpointed round
    (sampler + lr schedule + fedsim environment are pure functions of the
    step, so this reproduces the uninterrupted run exactly)."""
    from commefficient_tpu.train.runner import run_train_loop

    return run_train_loop(
        cfg, session, sampler, _CvHooks(session, test_ds, eval_batch_size),
        writer=writer, table=table, checkpointer=checkpointer,
        generated_by="train/cv_train",
    )


def main(argv=None, **overrides):
    from commefficient_tpu import native
    from commefficient_tpu.multihost import initialize_multihost
    from commefficient_tpu.parallel.mesh import initialize_distributed
    from commefficient_tpu.utils.platform import configure_compile_cache

    configure_compile_cache()
    cfg = parse_args(argv, **overrides)
    # --distributed: the checked multihost bring-up (names a missing
    # coordinator or a process-count/num_hosts mismatch); otherwise the
    # legacy env-driven path (no-op single-host)
    if not initialize_multihost(cfg):
        initialize_distributed()
    train, test, real, model, params, loss_fn, augment = build_model_and_data(cfg)
    print(
        f"dataset={cfg.dataset_name} (real={real}) model={cfg.model} "
        f"mode={cfg.mode} clients={train.num_clients} workers={cfg.num_workers} "
        f"devices={cfg.num_devices} host_loader={native.describe()}"
    )
    if not real:
        print("WARNING: real dataset not found on disk — synthetic stand-in "
              "(pipeline-correct; metrics are not paper numbers)")
    session, sampler = build_session_and_sampler(
        cfg, train, params, loss_fn, augment
    )
    bpr = session.bytes_per_round()
    print(f"grad_size D={session.grad_size}  upload/client/round="
          f"{bpr['upload_bytes']:,} B  download={bpr['download_bytes']:,} B")
    from commefficient_tpu.control import controller_header

    writer = MetricsWriter(make_logdir(cfg), cfg.tensorboard, cfg=cfg,
                           extra_header=controller_header(session))
    from commefficient_tpu.resilience import EXIT_PREEMPTED, PreemptShutdown
    from commefficient_tpu.utils.checkpoint import FedCheckpointer

    checkpointer = FedCheckpointer(cfg)
    try:
        # the shared runner owns both the end-of-training force-save and
        # the crash-path checkpointer close; the close here is the
        # idempotent belt for pre-loop failures
        val = train_loop(cfg, session, sampler, test, writer,
                         checkpointer=checkpointer)
    except PreemptShutdown as e:
        # preemption-safe shutdown (resilience/): metrics drained and a
        # checkpoint force-saved by the runner — exit with the DISTINCT
        # code so orchestrators retry with --resume instead of paging
        print(str(e))
        raise SystemExit(EXIT_PREEMPTED) from e
    finally:
        checkpointer.close()
        writer.close()
    print(f"final: val_loss={val['loss']:.4f} val_acc={val.get('accuracy', 0):.4f}")
    return val


if __name__ == "__main__":
    main()
