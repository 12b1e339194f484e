"""MoE dispatch correctness: the dropless expert layer vs a dense
per-token reference, load counts, aux loss, and no token dropped however
the router skews."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import MoEConfig, get_arch
from repro.models.layers import _act
from repro.models.moe import init_moe, moe_block


def dense_moe_reference(params, cfg, x):
    """Per-token loop over its top-k experts."""
    mo = cfg.moe
    B, S, D = x.shape
    N = B * S
    xf = x.reshape(N, D)
    logits = xf @ params["router"]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, eid = jax.lax.top_k(probs, mo.top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    out = np.zeros((N, D), np.float32)
    for n in range(N):
        for j in range(mo.top_k):
            e = int(eid[n, j])
            h = _act(xf[n] @ params["wg"][e], cfg.activation) * \
                (xf[n] @ params["wi"][e])
            out[n] += float(gate[n, j]) * np.asarray(h @ params["wo"][e])
    y = out.reshape(B, S, D)
    if mo.n_shared:
        h = _act(x @ params["shared_wg"], cfg.activation) * \
            (x @ params["shared_wi"])
        y = y + np.asarray(h @ params["shared_wo"])
    return y


@pytest.fixture(scope="module")
def setup():
    cfg = get_arch("mixtral-8x22b").smoke.with_(
        moe=MoEConfig(n_experts=4, top_k=2, n_shared=0, d_ff=32,
                      sharding="tp"))
    params, _ = init_moe(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 8, cfg.d_model))
    return cfg, params, x


class TestMoE:
    def test_matches_dense_reference_with_big_capacity(self, setup):
        cfg, params, x = setup
        y, aux, counts = moe_block(params, cfg, x)
        ref = dense_moe_reference(params, cfg, x)
        np.testing.assert_allclose(np.asarray(y), ref, atol=2e-4, rtol=2e-4)

    def test_counts_sum_to_nk(self, setup):
        cfg, params, x = setup
        _, _, counts = moe_block(params, cfg, x)
        N = x.shape[0] * x.shape[1]
        assert int(counts.sum()) == N * cfg.moe.top_k

    def test_aux_loss_positive_finite(self, setup):
        cfg, params, x = setup
        _, aux, _ = moe_block(params, cfg, x)
        assert np.isfinite(float(aux)) and float(aux) > 0

    def test_no_token_dropped_when_every_token_picks_the_same_experts(
            self, setup):
        """A router biased so that all 16 tokens route to experts 1 and 3
        (every token carries a large first feature, which the router
        scores for those two): each takes every token, and the output is
        still the per-token reference's (a capacity-bounded layer would
        drop most)."""
        cfg, params, x = setup
        x = x.at[..., 0].set(10.0)
        params = dict(params, router=params["router"].at[0, jnp.array(
            [1, 3])].add(5.0))
        y, _, counts = moe_block(params, cfg, x)
        assert counts.tolist() == [0, 16, 0, 16]
        ref = dense_moe_reference(params, cfg, x)
        np.testing.assert_allclose(np.asarray(y), ref, atol=2e-4, rtol=2e-4)

    def test_grouped_products_train_as_the_every_expert_loop(self, setup):
        """From ``GROUPED_TOKENS`` tokens on the layer sorts its routes
        through grouped products: output and gradients (weights and
        input, aux loss included) are the every-expert loop's."""
        from repro.models import moe as moe_mod
        cfg, params, _ = setup
        x = jax.random.normal(jax.random.key(2),
                              (2, moe_mod.GROUPED_TOKENS // 2, cfg.d_model))

        def loss(p, x):
            y, aux, _ = moe_block(p, cfg, x)
            return jnp.mean(jnp.square(y)) + aux

        grouped = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe_mod, "GROUPED_TOKENS", 1 << 30)
            loop = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        for a, b in zip(jax.tree.leaves(grouped), jax.tree.leaves(loop)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)

    def test_shared_experts_added(self):
        cfg = get_arch("deepseek-v2-lite-16b").smoke
        params, _ = init_moe(jax.random.key(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.key(1), (1, 4, cfg.d_model))
        y, aux, counts = moe_block(params, cfg, x)
        assert y.shape == x.shape
        assert counts.shape == (cfg.moe.n_experts,)

    def test_expert_counts_feed_analyzer(self, setup):
        """Per-expert token loads are per-'process' vectors for the
        dissimilarity pass (MoE imbalance as the paper's ST scenario)."""
        from repro.core import optics_cluster
        cfg, params, x = setup
        _, _, counts = moe_block(params, cfg, x)
        v = np.asarray(counts, np.float64)[:, None]
        res = optics_cluster(v)
        assert res.n_clusters >= 1
