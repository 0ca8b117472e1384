"""Plain float32 reference of GPT-2's next-token loss.

Radford et al. 2019: the decoder of Vaswani et al. 2017 with LayerNorm
moved to the input of each sub-block and one more after the last block,
learned positions, ``gelu_new``, the output projection tied to the token
embedding.  Dropout is off, as it is in the program's step.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference import common as ref


def loss(params, batch, config: dict):
    """``params``: the tree of ``GPTLMHeadModel``; ``batch``:
    ``input_ids`` of shape [B, S].  Position t predicts token t + 1."""
    p = ref.f32(params)
    eps = config["layer_norm_epsilon"]
    ids = batch["input_ids"]
    seq = ids.shape[1]
    wte = p["word_embeddings"]["embedding"]
    x = wte[ids] + p["position_embeddings"]["embedding"][:seq][None]

    def block(x, lp):
        x = x + ref.multi_head_attention(
            ref.layer_norm(x, lp["attention_norm"], eps), lp["attention"],
            causal=True)
        m = ref.dense(ref.layer_norm(x, lp["mlp_norm"], eps),
                      lp["intermediate"])
        return x + ref.dense(ref.gelu_tanh(m), lp["output"]), None

    x, _ = jax.lax.scan(jax.checkpoint(block), x,
                        ref.stack_layers(p, config["n_layer"]))
    x = ref.layer_norm(x, p["final_norm"], eps)
    logits = jnp.einsum("bsh,vh->bsv", x, wte, precision=ref.HI)
    return ref.cross_entropy(logits[:, :-1], ids[:, 1:]).mean()
