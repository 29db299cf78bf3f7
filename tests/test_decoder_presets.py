"""The Laguna presets and FedText's default rows are what they were before
the decoder's attention kind, QK norm, output gate, router form and head
chunk became fields of the configuration (PR 33) and FedText's
``doc_median`` an argument of the entry: the parameter tree (paths and
shapes), the tiny preset's loss to the last bit, every gradient to the last
bit, the lowered program's text, and the rows' bytes, against
``tests/golden/laguna_presets.json``, which was written from the commit
before (``python tests/test_decoder_presets.py`` prints it anew: needed
after a JAX upgrade moves the lowered text, and only then).

Since PR 35 (a fourth attention kind, ``block_length`` and ``mask_token`` as
fields, FedText's ``reserved``) the same is held for Keye's presets, whose
numbers were written from the commit before too, and the file also keeps
the trees of SDAR's presets as that PR built them.

PR 36 re-wrote one number, Laguna's ``tiny_lowered_sha``: the library's
attention kernels now name their output and log-sum-exp and every decoder's
``remat`` policy keeps them, so ``laguna_tiny``'s gradient calls the forward
kernel once a layer and lowers to another text (its loss and its gradients
are the bits they were). ``laguna_xs2`` opts out for its memory
(``attn_residuals_kept`` off); ``laguna_tiny`` has no such reason and keeps
the decoder's default, and with the preset's setting it still lowers to the
old text, which the file keeps as ``tiny_recomputed_lowered_sha``."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from commefficient_tpu.data import load_fed_text
from commefficient_tpu.models.keye import keye_tiny, keye_vl2
from commefficient_tpu.models.laguna import LagunaLM, laguna_tiny, laguna_xs2
from commefficient_tpu.models.sdar import sdar_30b_a3b, sdar_tiny
from commefficient_tpu.models.losses import causal_lm_loss

GOLDEN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "golden", "laguna_presets.json")


def _sha(*chunks):
    return hashlib.sha256(b"".join(chunks)).hexdigest()


def _tree(preset):
    shapes = jax.eval_shape(LagunaLM(preset()).init, jax.random.key(0),
                            jnp.zeros((1, 128), jnp.int32))
    return _sha(json.dumps([[n, list(a.shape)] for n, a in zip(
        weights.leaf_names(shapes), jax.tree.leaves(shapes))]).encode())


def _tiny_round(preset=laguna_tiny, prefix=""):
    cfg = preset(dtype=jnp.float32)
    model = LagunaLM(cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 128), 0, cfg.vocab_held)
    batch = {"input_ids": ids, "lm_labels": jnp.where(jnp.arange(128)[None, :] < 120, ids, -100)}
    params = weights.make(jax.eval_shape(model.init, jax.random.key(0), ids), 3, {"std": 0.02})
    f = jax.jit(jax.value_and_grad(causal_lm_loss(model.apply, "float32"), has_aux=True))
    (loss, _aux), grads = f(params, batch)
    return {prefix + "tiny_loss_bits": int(np.asarray(loss).view(np.uint32)),
            prefix + "tiny_grad_sha": _sha(*(np.asarray(g).tobytes()
                                             for g in jax.tree.leaves(grads))),
            prefix + "tiny_lowered_sha": _sha(f.lower(params, batch).as_text().encode())}


def _fedtext(**kw):
    train, test = load_fed_text(num_clients=4, seq_len=256, vocab=512, seed=7, **kw)
    return _sha(train.data["input_ids"].tobytes(), train.data["lm_labels"].tobytes(),
                test.data["input_ids"].tobytes())


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


PRESETS = {"laguna_xs2": laguna_xs2, "laguna_tiny": laguna_tiny, "keye_vl2": keye_vl2,
           "keye_tiny": keye_tiny, "sdar_30b_a3b": sdar_30b_a3b, "sdar_tiny": sdar_tiny}


@pytest.mark.parametrize("name", PRESETS)
def test_the_preset_builds_the_tree_it_built(golden, name):
    assert _tree(PRESETS[name]) == golden[name]


def test_only_the_preset_short_of_memory_gives_up_its_attention_residuals():
    """``laguna_xs2``'s cell holds 15.4 GB of the chip's 16.9 without them
    and 16.5 with (``models/laguna.py::laguna_xs2``); every other preset
    keeps the decoder's default."""
    assert {name for name, preset in PRESETS.items()
            if not preset().attn_residuals_kept} == {"laguna_xs2"}


def test_sdars_tree_is_keyes_without_the_index(golden):
    """The same decoder block: what differs is the mask and the objective."""
    def paths(preset):
        shapes = jax.eval_shape(LagunaLM(preset()).init, jax.random.key(0),
                                jnp.zeros((1, 128), jnp.int32))
        return {n: a.shape for n, a in zip(weights.leaf_names(shapes), jax.tree.leaves(shapes))}

    keye, sdar = paths(keye_vl2), paths(sdar_30b_a3b)
    assert sdar == {n: s for n, s in keye.items() if "/index_" not in n}
    assert len(keye) - len(sdar) == 4


def _laguna_tiny_as_xs2(**kw):
    """``laguna_tiny`` with ``laguna_xs2``'s one setting that is not a size."""
    return laguna_tiny(attn_residuals_kept=laguna_xs2().attn_residuals_kept, **kw)


@pytest.fixture(scope="module")
def tiny_rounds():
    recomputed = _tiny_round(_laguna_tiny_as_xs2)
    return {**_tiny_round(), **_tiny_round(keye_tiny, "keye_"),
            "tiny_recomputed_lowered_sha": recomputed["tiny_lowered_sha"]}


@pytest.mark.parametrize("what", [p + w for p in ("", "keye_") for w in (
    "tiny_loss_bits", "tiny_grad_sha", "tiny_lowered_sha")] + ["tiny_recomputed_lowered_sha"])
def test_the_tiny_round_is_the_one_it_was(golden, tiny_rounds, what):
    assert tiny_rounds[what] == golden[what]


def test_fedtext_at_the_default_median_makes_the_rows_it_made(golden):
    assert _fedtext() == golden["fedtext_sha"] == _fedtext(doc_median=300.0)
    assert _fedtext(doc_median=100.0) != golden["fedtext_sha"]
    assert _fedtext(reserved=0) == golden["fedtext_sha"] != _fedtext(reserved=1)


def test_the_entry_passes_the_median_and_defaults_to_300():
    from commefficient_tpu.train import lm_train

    argv = ["--model", "laguna_tiny", "--max_seq_len", "128", "--num_clients", "4",
            "--num_workers", "2"]
    cfg = lm_train.parse_args(argv, defaults=lm_train.DEFAULTS)
    assert cfg.doc_median == 300.0
    default = lm_train.build_model_and_data(cfg)[0].data["input_ids"]
    want = load_fed_text(num_clients=4, seq_len=128, vocab=256, seed=cfg.seed)[0].data["input_ids"]
    assert np.array_equal(default, want)
    cfg = lm_train.parse_args(argv + ["--doc_median", "40"], defaults=lm_train.DEFAULTS)
    assert not np.array_equal(lm_train.build_model_and_data(cfg)[0].data["input_ids"], default)


if __name__ == "__main__":
    print(json.dumps({**{name: _tree(preset) for name, preset in PRESETS.items()},
                      **_tiny_round(), **_tiny_round(keye_tiny, "keye_"),
                      "tiny_recomputed_lowered_sha":
                          _tiny_round(_laguna_tiny_as_xs2)["tiny_lowered_sha"],
                      "fedtext_sha": _fedtext()}))
