"""Federated data pipeline (L4): datasets, client sharding, round sampling.

Host-side numpy throughout (runs outside jit), mirroring the reference's
``data_utils/`` package (SURVEY.md §1 L4). Batches leave this layer as
``[num_workers, local_batch_size, ...]`` stacks ready for the device mesh.
"""

from commefficient_tpu.data.fed_dataset import FedDataset
from commefficient_tpu.data.sampler import FedSampler, prefetch
from commefficient_tpu.data.cifar import (
    load_fed_cifar10,
    load_fed_cifar100,
    augment_batch,
)
from commefficient_tpu.data.emnist import load_fed_emnist
from commefficient_tpu.data.imagenet import load_fed_imagenet
from commefficient_tpu.data.fedtext import load_fed_text
from commefficient_tpu.data.personachat import (
    load_fed_personachat,
    build_input_from_segments,
    special_ids,
    vocab_with_specials,
)

__all__ = [
    "FedDataset",
    "FedSampler",
    "prefetch",
    "load_fed_cifar10",
    "load_fed_cifar100",
    "augment_batch",
    "load_fed_emnist",
    "load_fed_imagenet",
    "load_fed_personachat",
    "load_fed_text",
    "build_input_from_segments",
    "special_ids",
    "vocab_with_specials",
]
