"""The sparse-attention indexer's operators (``ops/dsa.py``): the packed
mask, the selection against ``jax.lax.top_k`` (ties, short rows, a
threshold found a bit at a time), and the alignment loss with its
written-out backward against plain autodiff; XLA's forms and, in the
interpreter, the kernels' bodies."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import dsa


def plain_selection(q_i, k_i, w, topk):
    """``(keep [B, S, S], scores)`` by the definition: a ``top_k`` of the
    masked scores."""
    batch, seq = q_i.shape[:2]
    dots = jnp.einsum("btjd,bsd->btjs", q_i, k_i,
                      preferred_element_type=jnp.float32)
    scores = (jax.nn.relu(dots) * w[..., None]).sum(2)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    _, taken = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                             min(topk, seq))
    keep = jnp.zeros((batch, seq, seq), bool).at[
        jnp.arange(batch)[:, None, None], jnp.arange(seq)[None, :, None],
        taken].set(True) & causal
    return keep, scores


def indexer_inputs(seq, whole_numbers=False, batch=2, heads=3, dim=8):
    ks = jax.random.split(jax.random.PRNGKey(seq), 3)
    if whole_numbers:
        # Small whole numbers: every sum is exact in any order, and the
        # rows are full of ties (a relu's zeros among them).
        draw = lambda key, shape: jax.random.randint(key, shape, -2, 3)
        return (draw(ks[0], (batch, seq, heads, dim)).astype(jnp.bfloat16),
                draw(ks[1], (batch, seq, dim)).astype(jnp.bfloat16),
                draw(ks[2], (batch, seq, heads)).astype(jnp.float32) * 0.25)
    return (jax.random.normal(ks[0], (batch, seq, heads, dim)),
            jax.random.normal(ks[1], (batch, seq, dim)),
            jax.random.normal(ks[2], (batch, seq, heads)))


@pytest.mark.parametrize("seq,tile", [(64, None), (256, 64), (1024, None),
                                      (96, None)])
def test_the_packed_mask_round_trips(seq, tile):
    keep = jax.random.bernoulli(jax.random.PRNGKey(0), 0.3, (2, 40, seq))
    packed = dsa.pack_mask(keep, tile)
    assert packed.shape == (2, seq // 32, 40) and packed.dtype == jnp.int32
    assert (dsa.unpack_mask(packed, tile) == keep).all()
    # bit b of word row r of tile c is key c * tile + b * (tile / 32) + r
    tile = tile or dsa.key_tile_of(seq)
    c, rows = seq // tile - 1, tile // 32       # the last tile
    one = jnp.zeros((1, 1, seq), bool).at[0, 0, c * tile + 5 * rows + 1]
    words = np.asarray(dsa.pack_mask(one.set(True), tile))[0, :, 0]
    assert words[c * rows + 1] == 1 << 5 and np.count_nonzero(words) == 1


def test_key_tile_by_hand():
    assert [dsa.key_tile_of(s) for s in (16384, 512, 256, 96, 640)] \
        == [512, 512, 256, 96, 320]
    with pytest.raises(ValueError, match="whole words"):
        dsa.key_tile_of(100)
    assert dsa.selected_pairs(16384, 2048) == 31_458_304
    assert dsa.selected_pairs(64, 2048) == dsa.causal_pairs(64) == 64 * 65 // 2


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("seq,topk,tile,whole_numbers", [
    (256, 40, 64, False),      # crosses blocks of queries and tiles of keys
    (256, 40, 64, True),       # tied rows: the lower position first
    (128, 200, None, False),   # rows shorter than topk keep every key
], ids=["plain", "tied", "short"])
def test_the_selection_is_top_ks(kernel, seq, topk, tile, whole_numbers):
    q_i, k_i, w = indexer_inputs(seq, whole_numbers)
    keep, scores = plain_selection(q_i, k_i, w, topk)
    if whole_numbers:   # the case is what it says: ties AT a threshold
        least = jnp.sort(jnp.where(keep, scores, jnp.inf), -1)[..., 0]
        left_out = (~keep & jnp.tril(jnp.ones((seq, seq), bool))
                    & (scores == least[..., None])).any(-1)
        assert int(left_out.sum()) > seq // 2
    packed, lse = jax.jit(lambda *a: dsa.select(
        *a, topk, interpret=kernel, key_tile=tile))(q_i, k_i, w)
    assert (dsa.unpack_mask(packed, tile) == keep).all()
    assert np.asarray(keep.sum(-1))[0].tolist() \
        == [min(t + 1, topk) for t in range(seq)]
    want = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), -1)
    np.testing.assert_allclose(lse, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        dsa.selected_lse(q_i, k_i, w, packed, tile), want, rtol=1e-5,
        atol=1e-5)


def plain_alignment(q_i, k_i, w, q, k, topk, scale):
    """``mean_t KL(pbar || softmax(I))`` on ``S_t`` by the definition,
    for ``jax.grad``."""
    keep, _ = plain_selection(*jax.lax.stop_gradient((q_i, k_i, w)), topk)
    dots = jnp.einsum("btjd,bsd->btjs", q_i, k_i)
    scores = (jax.nn.relu(dots) * w[..., None]).sum(2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q,
                   jnp.repeat(k, q.shape[2] // k.shape[2], 2)) * scale
    pbar = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1).mean(1)
    pbar = pbar / pbar.sum(-1, keepdims=True)
    log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
    some = keep & (pbar > 0)
    return jnp.where(some, pbar * (jnp.log(jnp.where(some, pbar, 1.0))
                                   - jnp.where(some, log_q, 0.0)),
                     0.0).sum(-1).mean()


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_the_alignment_loss_and_its_written_out_backward(kernel):
    """Value and the three gradients against autodiff of the definition,
    at a shape that crosses blocks of queries and tiles of keys; nothing
    reaches the main attention's arrays."""
    seq, topk, tile, scale = 256, 40, 64, 16 ** -0.5
    q_i, k_i, w = indexer_inputs(seq)
    w = w / np.sqrt(24.0)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    q = jax.random.normal(ks[0], (2, seq, 4, 16))
    k = jax.random.normal(ks[1], (2, seq, 2, 16))

    def program(q_i, k_i, w, q, k):
        packed, lse_i = dsa.select(q_i, k_i, w, topk, key_tile=tile)
        keep = dsa.unpack_mask(packed, tile)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, 2)) * scale
        lse = jax.nn.logsumexp(jnp.where(keep[:, None], s, -jnp.inf), -1)
        return dsa.indexer_loss(q_i, k_i, w, q, k, lse, packed, lse_i, scale,
                                key_tile=tile, interpret=kernel)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.jit(jax.value_and_grad(
            lambda *a: plain_alignment(*a, q, k, topk, scale),
            (0, 1, 2)))(q_i, k_i, w)
        got, got_g = jax.jit(jax.value_and_grad(program, (0, 1, 2, 3, 4)))(
            q_i, k_i, w, q, k)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for g, wg in zip(got_g[:3], want_g):
        np.testing.assert_allclose(g, wg, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(wg).max()))
    assert not np.asarray(got_g[3]).any() and not np.asarray(got_g[4]).any()


def test_the_selection_writes_what_the_gauge_says():
    assert dsa.select_bytes(1, 16384) == 16384 // 32 * 16384 * 4 + 16384 * 4
    q_i, k_i, w = indexer_inputs(64)
    packed, lse = dsa.select(q_i, k_i, w, 24)
    assert packed.nbytes + lse.nbytes == dsa.select_bytes(2, 64)
