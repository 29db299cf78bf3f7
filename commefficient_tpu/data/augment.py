"""What a round's feed may do to its rows besides gathering them, as a plan.

An augmenter draws a round's randomness on the host as a few small
per-sample arrays (``plan``: leading dimension ``n``, one entry a sample, in
the order of the round's flat ``[W * B]`` gather) and realizes them two ways
that give the same batch bit for bit: ``apply`` in numpy, for the sampler's
host path, and ``device_apply`` as traced ``jax.numpy``, inside the compiled
round under ``data_gather`` (``parallel/api.py``: only the row indices and
the plan cross the host->device link). It works on named keys of the batch,
``[n, ...]`` each: ``reads`` names the dataset keys it looks at, and what it
hands back is a dict of keys that replace a key of the dataset (the images
of ``CifarAugment``, ``ImageNetAugment``) or come beside them (the noise of
``fedtext.BlockNoise``). ``FedSampler`` draws the plan after the clients'
and the rows' draws of the same ``default_rng((seed, round))``.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np


class BatchAugment:
    reads: Tuple[str, ...] = ()

    def accepts(self, data: Dict[str, np.ndarray]) -> bool:
        """Whether ``data`` (a dataset's arrays) holds what this reads, in a
        form both paths take."""
        return all(isinstance(data.get(k), np.ndarray) for k in self.reads)

    def plan_args(self, data) -> tuple:
        """What ``plan`` takes after ``(rng, n)``, read off the rows' shapes."""
        return ()

    def plan(self, rng: np.random.Generator, n: int, *args) -> tuple:
        raise NotImplementedError

    def apply(self, batch: Dict[str, np.ndarray], *plan) -> Dict[str, np.ndarray]:
        """``{key: [n, ...]}``, the keys it replaces or adds, in numpy."""
        raise NotImplementedError

    def device_apply(self, batch, *plan):
        """``apply`` as traced ``jax.numpy`` ops."""
        raise NotImplementedError

    def gather_apply(self, data, idx: np.ndarray, plan) -> Optional[Dict[str, np.ndarray]]:
        """``apply`` fused with the gather of rows ``idx`` where a native
        kernel does both at once; ``None`` where none does (the sampler then
        gathers and calls ``apply``)."""
        return None

    def __call__(self, batch, rng: np.random.Generator):
        """The per-batch form: draw a plan for ``batch`` and apply it."""
        n = len(batch[self.reads[0]])
        return {**batch, **self.apply(batch, *self.plan(rng, n, *self.plan_args(batch)))}


class ImageAugment(BatchAugment):
    """An augmenter of the images under ``"x"`` ``[n, h, w, c]``: a subclass
    gives ``Plan`` (the named tuple its ``plan(rng, n, h, w)`` returns) and
    the pixel paths ``apply_pixels(x, plan)``, ``device_pixels(x, *plan)``
    and ``gather_pixels(data_x, idx, plan)`` (native; ``None`` without the
    library)."""

    reads = ("x",)
    Plan: type    # the subclass's named tuple

    def accepts(self, data) -> bool:
        x = data.get("x")
        return (isinstance(x, np.ndarray) and x.ndim == 4
                and x.dtype in (np.float32, np.uint8))

    def plan_args(self, data) -> tuple:
        return tuple(data["x"].shape[1:3])

    def apply(self, batch, *plan):
        return {"x": self.apply_pixels(np.ascontiguousarray(batch["x"]), self.Plan(*plan))}

    def device_apply(self, batch, *plan):
        return {"x": self.device_pixels(batch["x"], *plan)}

    def gather_apply(self, data, idx, plan):
        out = self.gather_pixels(data["x"], idx, plan)
        return None if out is None else {"x": out}
