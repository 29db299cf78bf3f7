"""The least bytes the Laguna cell's grouped expert products must move over
HBM in a round, as a function of the *work* and never of the implementation
(``benchmark/kernel_bytes.py`` says the same of the sketch's kernels). A true
lower bound at the precision the configuration states (bfloat16 operands, a
weight's gradient rounded to bfloat16 where it leaves its product), every
input read once and every output written once, the activation between the
products never leaving the chip, **recomputation not counted**: so a share
over 100 % in ``benchmark/layers/kernel_hbm_share.py`` means the scope does
not cover the work.

Each client is routed apart (its gradient is clipped apart), so each client
reads every held expert's three matrices for itself, in the forward pass and
again in the backward one, and writes their gradients once. Rows are counted
at the expected held assignments of a uniform router, as
``benchmark/flops_laguna.py`` counts them; at this cell's 128 rows an expert
they are an eighth of the bytes, so the router's skew moves the bound little.

``clients`` is the cohort, from the traffic file's ``reference`` group; the
model's sizes are the configuration's ``flops_kwargs``.
"""

from __future__ import annotations

import json
import os

BF16 = 2
CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs",
                      "laguna_xs2_fedtext.json")


def moe_experts_bytes(*, clients, **_):
    """Forward: read the weights and the rows, write the rows' outputs.
    Backward: read the weights, the rows and the outputs' cotangents, write
    the rows' cotangents and the weights' gradients."""
    with open(CONFIG) as f:
        kw = json.load(f)["flops_kwargs"]
    sparse = sum(1 for t in kw["mlp_layer_types"] if t == "sparse")
    weights = 3 * kw["experts_held"] * kw["hidden"] * kw["expert_width"]
    rows = kw["rows_per_client"] * kw["seq"] * kw["top_k"] * kw["experts_held"] / kw["num_experts"]
    return BF16 * clients * sparse * (3 * weights + 5 * rows * kw["hidden"])
