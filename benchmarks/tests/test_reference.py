"""The plain reference against ``llama.forward_logits`` in float32 at toy
size, for an MHA and a GQA shape: a wrong rotary convention, head grouping
or weight orientation shows here, before a chip call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmarks.reference import dense_decoder

SHAPES = {
    "mha": dict(num_attention_heads=4, num_key_value_heads=4),
    "gqa": dict(num_attention_heads=8, num_key_value_heads=2),
}


@pytest.mark.parametrize("kind", sorted(SHAPES))
def test_reference_matches_forward_logits(kind):
    from picotron_tpu.config import Config
    from picotron_tpu.models import llama
    from picotron_tpu.topology import topology_from_config
    from picotron_tpu.utils import shard_map

    model = dict(SHAPES[kind], num_hidden_layers=3, hidden_size=128,
                 intermediate_size=320, vocab_size=384, rms_norm_eps=1e-5,
                 rope_theta=1e4 if kind == "mha" else 1e6,
                 max_position_embeddings=64)
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True},
        "model": dict(model, name=kind, dtype="float32",
                      attention_impl="sdpa"),
        "training": {"seq_length": 48}, "dataset": {"name": "synthetic"}})
    topo = topology_from_config(cfg, devices=jax.devices()[:1])
    params = llama.init_params(jax.random.PRNGKey(3), cfg.model)
    # norms are all ones at init: perturb them so a dropped weight shows
    params = jax.tree.map(
        lambda v: v + 0.1 * jax.random.normal(jax.random.PRNGKey(v.size),
                                              v.shape, v.dtype), params)
    tokens = np.random.default_rng(0).integers(0, 384, (2, 48)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(shard_map(
            lambda p, t: llama.forward_logits(p, t, cfg), topo.mesh,
            in_specs=(llama.param_pspecs(cfg.model), P()),
            out_specs=P()))(params, jnp.asarray(tokens)))
    got = dense_decoder.forward_logits(params, tokens, model)
    assert got.shape == want.shape == (2, 48, 384)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # and the loss is the mean cross-entropy of those logits
    targets = np.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(jnp.asarray(want), axis=-1)
    ce = -np.mean(np.take_along_axis(np.asarray(logp), targets[..., None], -1))
    assert dense_decoder.loss(params, tokens, targets, model) == \
        pytest.approx(float(ce), abs=1e-4)
