"""Model-zoo and sharded-training tests (tiny shapes, 8-dev CPU mesh)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import (BertForMaskedLM, MnistMLP, ResNet18,
                                bert_tiny_config, mlm_loss)


def test_resnet18_forward():
    model = ResNet18(num_classes=10, dtype=jnp.float32)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    y = model.apply(variables, x, train=False)
    assert y.shape == (2, 10)
    assert np.isfinite(np.asarray(y)).all()


def test_bert_tiny_forward_and_loss():
    cfg = bert_tiny_config()
    model = BertForMaskedLM(cfg)
    ids = jnp.ones((2, 16), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids, deterministic=True)
    logits = model.apply(variables, ids, deterministic=True)
    assert logits.shape == (2, 16, cfg.vocab_size)
    labels = jnp.zeros((2, 16), jnp.int32)
    mask = jnp.ones((2, 16), jnp.int32)
    loss = mlm_loss(logits, labels, mask)
    assert np.isfinite(float(loss))


def test_bert_tied_embeddings():
    cfg = bert_tiny_config()
    model = BertForMaskedLM(cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids, deterministic=True)
    flat = jax.tree_util.tree_leaves(variables["params"])
    # The MLM head must not own a (hidden, vocab) projection — tied.
    assert not any(p.shape == (cfg.hidden_size, cfg.vocab_size)
                   for p in flat)


def test_factor_mesh_axes():
    from horovod_tpu.training import factor_mesh_axes
    assert factor_mesh_axes(8) == {"dp": 2, "tp": 2, "sp": 2}
    assert factor_mesh_axes(4) == {"dp": 2, "tp": 2, "sp": 1}
    assert factor_mesh_axes(2) == {"dp": 2, "tp": 1, "sp": 1}
    assert factor_mesh_axes(1) == {"dp": 1, "tp": 1, "sp": 1}
    assert factor_mesh_axes(6) == {"dp": 6, "tp": 1, "sp": 1}


def test_bert_sharded_train_step_loss_decreases():
    from horovod_tpu.training import (make_bert_batch,
                                      make_bert_pretrain_step)
    from horovod_tpu.models.bert import bert_tiny_config
    from horovod_tpu.parallel.mesh import build_mesh

    cfg = bert_tiny_config(max_position_embeddings=32)
    mesh = build_mesh({"dp": 2, "tp": 2, "sp": 2})
    make_jitted, batch_sharding = make_bert_pretrain_step(
        cfg, mesh, learning_rate=1e-2)
    batch = make_bert_batch(8, 32, cfg.vocab_size)
    batch = jax.tree.map(lambda x: jax.device_put(x, batch_sharding),
                         batch)
    init_fn, step_fn = make_jitted(batch)
    state = init_fn(jax.random.PRNGKey(0), batch)
    losses = []
    for _ in range(10):
        state, loss = step_fn(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_sharding_rules_applied():
    from horovod_tpu.parallel.sharding import (bert_partition_rules,
                                               infer_shardings)
    from horovod_tpu.parallel.mesh import build_mesh
    from horovod_tpu.models.bert import bert_tiny_config

    cfg = bert_tiny_config()
    model = BertForMaskedLM(cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids,
                           deterministic=True))["params"]
    mesh = build_mesh({"dp": 2, "tp": 2, "sp": 2})
    shardings = infer_shardings(params, mesh, bert_partition_rules())
    flat = dict(
        (("/".join(str(getattr(k, "key", k)) for k in path)), s)
        for path, s in jax.tree_util.tree_flatten_with_path(shardings)[0])
    qk = [s for p, s in flat.items() if p.endswith("query/kernel")]
    assert qk and all("tp" in str(s.spec) for s in qk)
    emb = [s for p, s in flat.items()
           if p.endswith("word_embeddings/embedding")]
    assert emb and "tp" in str(emb[0].spec)


# ---------------------------------------------------------------------------
# GPT decoder family
# ---------------------------------------------------------------------------

def test_gpt_tiny_forward_and_loss():
    from horovod_tpu.models import (GPTLMHeadModel, gpt_tiny_config,
                                    lm_loss)
    cfg = gpt_tiny_config()
    model = GPTLMHeadModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(1), ids)["params"]
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    loss = lm_loss(logits, ids)
    assert loss.shape == () and float(loss) > 0


def test_gpt_causality():
    """Changing a future token must not change logits at earlier
    positions (causal mask correctness)."""
    from horovod_tpu.models import GPTLMHeadModel, gpt_tiny_config
    cfg = gpt_tiny_config()
    model = GPTLMHeadModel(cfg)
    ids = jnp.zeros((1, 12), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    base = model.apply({"params": params}, ids)
    mutated = ids.at[0, 8].set(5)
    out = model.apply({"params": params}, mutated)
    np.testing.assert_allclose(np.asarray(base[0, :8]),
                               np.asarray(out[0, :8]), atol=1e-5)
    assert not np.allclose(np.asarray(base[0, 8:]),
                           np.asarray(out[0, 8:]))


def test_gpt_tied_lm_head():
    from horovod_tpu.models import GPTLMHeadModel, gpt_tiny_config
    cfg = gpt_tiny_config()
    model = GPTLMHeadModel(cfg)
    ids = jnp.zeros((1, 4), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    flat = jax.tree_util.tree_leaves_with_path(params)
    names = ["/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in flat]
    # No separate lm_head kernel: the output projection reuses the
    # word embedding.
    assert not any("lm_head" in n for n in names), names


def test_gpt_sharding_rules_applied():
    from horovod_tpu.parallel.sharding import (gpt_partition_rules,
                                               infer_shardings)
    from horovod_tpu.parallel.mesh import build_mesh
    from horovod_tpu.models import GPTLMHeadModel, gpt_tiny_config

    cfg = gpt_tiny_config()
    model = GPTLMHeadModel(cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
    mesh = build_mesh({"dp": 2, "tp": 2, "sp": 2})
    shardings = infer_shardings(params, mesh, gpt_partition_rules())
    flat = dict(
        (("/".join(str(getattr(k, "key", k)) for k in path)), s)
        for path, s in jax.tree_util.tree_flatten_with_path(shardings)[0])
    qk = [s for p, s in flat.items() if p.endswith("query/kernel")]
    assert qk and all("tp" in str(s.spec) for s in qk)
    emb = [s for p, s in flat.items()
           if p.endswith("word_embeddings/embedding")]
    assert emb and "tp" in str(emb[0].spec)


def test_gpt_sharded_train_step_loss_decreases():
    """Full dp x tp sharded LM training step on the virtual mesh
    (shared make_gpt_train_step infrastructure)."""
    from horovod_tpu.models import gpt_tiny_config
    from horovod_tpu.parallel.mesh import build_mesh
    from horovod_tpu.training import make_gpt_train_step

    cfg = gpt_tiny_config()
    mesh = build_mesh({"dp": 4, "tp": 2})
    init_fn, step_fn, batch_sharding = make_gpt_train_step(
        cfg, mesh, learning_rate=1e-2)
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0,
                             cfg.vocab_size)
    ids = jax.device_put(ids, batch_sharding)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step_fn(params, opt_state, ids)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_gpt_fsdp_train_step_shards_params_and_learns():
    """FSDP (ZeRO-3) schedule: params and optimizer state shard over
    the fsdp axis (not replicated), the batch rides the same axis, and
    the loss still decreases — the reduce-scatter/all-gather schedule
    the reference never exposed (SURVEY §2.3 FSDP row)."""
    import numpy as np
    from horovod_tpu.models import gpt_tiny_config
    from horovod_tpu.parallel.mesh import build_mesh
    from horovod_tpu.training import make_gpt_train_step

    cfg = gpt_tiny_config()
    mesh = build_mesh({"fsdp": 4, "tp": 2})
    init_fn, step_fn, batch_sharding = make_gpt_train_step(
        cfg, mesh, learning_rate=1e-2, fsdp="fsdp")
    ids = jax.random.randint(jax.random.PRNGKey(0), (8, 16), 0,
                             cfg.vocab_size)
    ids = jax.device_put(ids, batch_sharding)
    params, opt_state = init_fn(jax.random.PRNGKey(1), ids)

    # At least one large kernel is genuinely fsdp-sharded, and its
    # optimizer moment inherits that sharding.
    flat = jax.tree_util.tree_leaves_with_path(params)
    sharded = [(jax.tree_util.keystr(p), l) for p, l in flat
               if "fsdp" in str(l.sharding.spec)]
    assert sharded, "no parameter sharded over the fsdp axis"
    name0, leaf0 = sharded[0]
    mu = jax.tree_util.tree_leaves_with_path(opt_state[0].mu)
    mu_match = [l for p, l in mu if jax.tree_util.keystr(p) == name0]
    assert mu_match and mu_match[0].sharding == leaf0.sharding

    losses = []
    for _ in range(8):
        params, opt_state, loss = step_fn(params, opt_state, ids)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# BERT's dropout: the masks' bits come from XLA's RngBitGenerator
# ---------------------------------------------------------------------------

def _bert_dropout_step(rate, batch_size=8, seq_len=32):
    """The tiny BERT step on one device, dropout at ``rate`` at every
    site, with its state and batch."""
    from horovod_tpu.training import (make_bert_batch,
                                      make_bert_pretrain_step)
    from horovod_tpu.parallel.mesh import build_mesh

    cfg = bert_tiny_config(hidden_dropout=rate, attention_dropout=rate,
                           max_position_embeddings=seq_len)
    mesh = build_mesh({"dp": 1}, jax.devices()[:1])
    make_jitted, batch_sharding = make_bert_pretrain_step(
        cfg, mesh, donate=False)
    batch = make_bert_batch(batch_size, seq_len, cfg.vocab_size)
    batch = jax.tree.map(lambda x: jax.device_put(x, batch_sharding),
                         batch)
    init_fn, step_fn = make_jitted(batch)
    return cfg, step_fn, init_fn(jax.random.PRNGKey(0), batch), batch


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) \
                    else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _tensor_sizes(line):
    """Element counts of the ``tensor<..>`` types on a line of MLIR."""
    import re
    return [int(np.prod([int(d) for d in dims.split("x") if d] or [1]))
            for dims in re.findall(r"tensor<((?:\d+x)*)[a-z]", line)]


def test_bert_dropout_bits_come_from_rng_bit_generator():
    """Every dropout site (3 a layer + 1) draws its mask from an
    ``rbg`` key at the site's shape, the lowered step makes those bits
    with ``rng_bit_generator``, and what threefry is left (folding the
    step and the module path into the key) works on a key's few words,
    never on an array of a mask's size."""
    from collections import Counter
    b, s = 8, 32
    cfg, step_fn, state, batch = _bert_dropout_step(0.1, b, s)
    hidden = (b, s, cfg.hidden_size)
    probs = (b, cfg.num_heads, s, s)

    draws = [(str(e.invars[0].aval.dtype), tuple(e.params["shape"]))
             for e in _eqns(jax.make_jaxpr(step_fn)(state, batch).jaxpr)
             if e.primitive.name == "random_bits"]
    assert Counter(draws) == {
        ("key<rbg>", hidden): 2 * cfg.num_layers + 1,
        ("key<rbg>", probs): cfg.num_layers}, draws

    text = step_fn.lower(state, batch).as_text()
    generated = {line.rsplit("tensor<", 1)[1].split("xui32")[0]
                 for line in text.splitlines()
                 if "stablehlo.rng_bit_generator" in line}
    assert generated == {"x".join(map(str, shape))
                         for shape in (hidden, probs)}, generated
    threefry = [line for line in text.splitlines() if "threefry" in line]
    assert threefry, "the key is still folded with threefry"
    assert max(n for line in threefry for n in _tensor_sizes(line)) <= 4


def test_bert_without_dropout_holds_no_generator():
    _, step_fn, state, batch = _bert_dropout_step(0.0)
    assert not any(
        e.primitive.name == "random_bits"
        for e in _eqns(jax.make_jaxpr(step_fn)(state, batch).jaxpr))
    assert "rng_bit_generator" not in step_fn.lower(state, batch).as_text()


def test_bert_dropout_replays_a_step_and_moves_on_at_the_next():
    """The masks are a function of ``(dropout_seed, state.step)``."""
    _, step_fn, state, batch = _bert_dropout_step(0.1)
    state1, first = step_fn(state, batch)
    _, again = step_fn(state, batch)
    assert float(first) == float(again)
    # The same parameters at the next step: only the masks differ.
    _, other = step_fn(state.replace(step=state.step + 1), batch)
    assert float(other) != float(first)
    assert int(state1.step) == int(state.step) + 1


def test_bert_dropout_mask_has_the_exact_rate_and_scale():
    """A million draws under the step's kind of key: the kept share
    within four binomial standard deviations of 0.9, the kept values
    scaled by 1 / 0.9."""
    import flax.linen as nn
    n, keep = 1 << 20, 0.9
    key = jax.random.fold_in(jax.random.key(0, impl="rbg"), 7)
    y = np.asarray(nn.Dropout(1.0 - keep).apply(
        {}, jnp.ones((1024, 1024), jnp.float32), deterministic=False,
        rngs={"dropout": key}))
    kept = y != 0.0
    assert abs(kept.mean() - keep) < 4 * np.sqrt(keep * (1 - keep) / n)
    np.testing.assert_allclose(y[kept], 1.0 / keep, rtol=1e-6)
