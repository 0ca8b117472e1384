"""Plain float32 reference of BERT's masked-language-model loss.

Devlin et al. 2018 (post-LayerNorm encoder of Vaswani et al. 2017) with
the head of google-research/bert ``run_pretraining.py``
``get_masked_lm_output``: dense, activation, LayerNorm, the tied word
embedding, an output bias.  Departure, shared with the program and
stated in the configuration file: the activation is the tanh form of
GELU where BERT's ``hidden_act`` is the erf form.  Dropout is off (the
comparison is deterministic).  No next-sentence head.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference import common as ref

LAYER_NORM_EPS = 1e-12  # google-research/bert modeling.py layer_norm


def loss(params, batch, config: dict):
    """``params``: the tree of ``BertForMaskedLM``; ``batch``:
    ``input_ids``, ``labels``, ``mask`` of shape [B, S]."""
    p = ref.f32(params)
    enc = p["encoder"]
    ids = batch["input_ids"]
    seq = ids.shape[1]
    word = enc["word_embeddings"]["embedding"]
    x = (word[ids] + enc["position_embeddings"]["embedding"][:seq][None]
         + enc["token_type_embeddings"]["embedding"][0])
    x = ref.layer_norm(x, enc["embeddings_norm"], LAYER_NORM_EPS)

    def layer(x, lp):
        a = ref.multi_head_attention(x, lp["attention"], causal=False)
        x = ref.layer_norm(x + a, lp["attention_norm"], LAYER_NORM_EPS)
        h = ref.dense(ref.gelu_tanh(ref.dense(x, lp["intermediate"])),
                      lp["output"])
        return ref.layer_norm(x + h, lp["output_norm"], LAYER_NORM_EPS), None

    x, _ = jax.lax.scan(
        jax.checkpoint(layer), x,
        ref.stack_layers(enc, config["num_hidden_layers"]))
    t = ref.layer_norm(ref.gelu_tanh(ref.dense(x, p["mlm_transform"])),
                       p["mlm_norm"], LAYER_NORM_EPS)
    logits = jnp.einsum("bsh,vh->bsv", t, word,
                        precision=ref.HI) + p["mlm_bias"]
    nll = ref.cross_entropy(logits, batch["labels"])
    mask = batch["mask"].astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
