"""The toy model of each block but Llama, where its own test file
(``test_<block>.py``) and the matrix of what every such block refuses
(``test_block_refusals.py``) both read it: published keys at toy widths, in
float32, by ``model_type``."""

from picotron_tpu.config import Config

YARN = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 64}
SPARSE = dict(kernel_size=8, kernel_stride=4, block_size=16, init_blocks=1,
              window_size=32, topk=4, dense_len=64)
S, F = "sliding_attention", "full_attention"  # ``afmoe``'s kinds of layer

TOYS = {
    "deepseek_v32": dict(
        name="toy-dsv32", model_type="deepseek_v32", num_hidden_layers=3,
        first_k_dense_replace=1, hidden_size=128, num_attention_heads=8,
        num_key_value_heads=8, intermediate_size=256, vocab_size=512,
        rms_norm_eps=1e-6, rope_theta=10000.0, max_position_embeddings=512,
        dtype="float32", q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=16,
        index_topk=16, n_routed_experts=2, ep_size=4, ep_rank=0,
        n_shared_experts=1, num_experts_per_tok=2, moe_intermediate_size=64,
        n_group=4, topk_group=2, routed_scaling_factor=2.5, rope_scaling=YARN),
    "granitemoehybrid": dict(
        name="toy-granite", model_type="granitemoehybrid", num_hidden_layers=5,
        layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=32, vocab_size=256, rms_norm_eps=1e-5,
        max_position_embeddings=256, dtype="float32", mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_d_conv=4, mamba_chunk_size=8,
        num_local_experts=3, ep_size=2, ep_rank=0, num_experts_per_tok=2,
        shared_intermediate_size=48, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.0625,
        logits_scaling=16.0, position_embedding_type="nope",
        tie_word_embeddings=True),
    "minicpm_sala": dict(
        name="toy-sala", model_type="minicpm_sala", num_hidden_layers=7,
        mixer_types=["minicpm4", "lightning-attn", "lightning-attn",
                     "lightning-attn", "minicpm4", "minicpm4",
                     "lightning-attn"],
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, vocab_size=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, max_position_embeddings=512, dtype="float32",
        lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
        attn_use_rope=False, attn_use_output_gate=True,
        qk_norm=True, use_output_norm=True, use_output_gate=True,
        scale_emb=12.0, scale_depth=1.4, dim_model_base=16, mup_denominator=32,
        sparse_config=SPARSE, first_layer=9, total_layers=32),
    "afmoe": dict(
        name="toy-afmoe", model_type="afmoe", num_hidden_layers=4,
        layer_types=[S, S, F, S], num_dense_layers=1, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        intermediate_size=96, vocab_size=256, rms_norm_eps=1e-5,
        rope_theta=10000.0, max_position_embeddings=256, dtype="float32",
        sliding_window=16, num_experts=2, ep_size=4, ep_rank=0,
        num_experts_per_tok=2, num_shared_experts=1, moe_intermediate_size=32,
        route_scale=2.448, mup_enabled=True),
    "mimo_v2": dict(
        name="toy-mimo", model_type="mimo_v2", num_hidden_layers=5,
        hybrid_layer_pattern=[0, 1, 1, 0, 1], moe_layer_freq=[0, 1, 1, 1, 1],
        hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
        head_dim=24, v_head_dim=16, swa_num_attention_heads=8,
        swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
        partial_rotary_factor=0.334, rope_theta=1e7, swa_rope_theta=1e4,
        sliding_window=6, attention_value_scale=0.707,
        add_swa_attention_sink_bias=True, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=2, ep_size=4, ep_rank=0,
        num_experts_per_tok=2, vocab_size=256, rms_norm_eps=1e-5,
        max_position_embeddings=256, dtype="float32"),
    "KeyeVL2": dict(
        name="toy-keye", model_type="KeyeVL2", num_hidden_layers=3,
        hidden_size=128, num_attention_heads=8, num_key_value_heads=2,
        head_dim=32, intermediate_size=256, vocab_size=512, rms_norm_eps=1e-6,
        rope_theta=10000.0, max_position_embeddings=512, dtype="float32",
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 4,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                   "q_chunk_size": 8, "topk": 16},
        rope_scaling={"mrope_section": [4, 6, 6], "rope_type": "default",
                      "type": "default"},
        num_experts=4, num_local_experts=16, ep_size=4, ep_rank=0,
        num_experts_per_tok=4, moe_intermediate_size=64, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=[], first_layer=3,
        total_layers=12),
    "nemotron_h": dict(
        name="toy-nemotron", model_type="nemotron_h", num_hidden_layers=7,
        hybrid_override_pattern="MEMEM*E", hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=32, vocab_size=256, rms_norm_eps=1e-5,
        layer_norm_epsilon=1e-5, max_position_embeddings=256, dtype="float32",
        mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16, n_groups=4,
        conv_kernel=4, chunk_size=8, n_routed_experts=3, ep_size=2, ep_rank=0,
        num_experts_per_tok=2, moe_intermediate_size=32, moe_latent_size=32,
        moe_shared_expert_intermediate_size=48, n_shared_experts=1,
        routed_scaling_factor=2.5, mlp_hidden_act="relu2"),
    "solar_open2": dict(
        name="toy-solar", model_type="solar_open2", num_hidden_layers=8,
        gqa_layers=[0, 4], gqa_interval=3, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        intermediate_size=160, vocab_size=256, rms_norm_eps=1e-5,
        max_position_embeddings=256, dtype="float32",
        linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                            "num_heads": 4, "num_kv_heads": None},
        use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
        kda_allow_neg_eigval=True, n_routed_experts=3, ep_size=2, ep_rank=0,
        num_experts_per_tok=2, moe_intermediate_size=32, n_shared_experts=1,
        routed_scaling_factor=1.0, norm_topk_prob=True,
        first_k_dense_replace=0),
    "sdar_moe": dict(
        name="toy-sdar", model_type="sdar_moe", num_hidden_layers=3,
        hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
        head_dim=16, intermediate_size=96, vocab_size=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, max_position_embeddings=256, dtype="float32",
        num_experts=4, num_local_experts=16, ep_size=4, ep_rank=0,
        num_experts_per_tok=4, moe_intermediate_size=32, norm_topk_prob=True,
        decoder_sparse_step=1, mlp_only_layers=[], first_layer=3,
        total_layers=12, block_length=4, denoising_steps=4,
        remasking="low_confidence_dynamic", confidence_threshold=0.9,
        mask_token_id=255),
    # five query heads a K/V head, two B/C groups, d_ssm 96 != 2 x hidden 80:
    # the published ratios; the multipliers as published
    "falcon_h1": dict(
        name="toy-falcon", model_type="falcon_h1", num_hidden_layers=3,
        hidden_size=80, num_attention_heads=10, num_key_value_heads=2,
        head_dim=16, intermediate_size=96, vocab_size=256, rms_norm_eps=1e-5,
        rope_theta=1e11, max_position_embeddings=256, dtype="float32",
        mamba_n_heads=8, mamba_d_head=12, mamba_d_ssm=96, mamba_d_state=16,
        mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8, mamba_expand=2,
        embedding_multiplier=5.656854249492381,
        key_multiplier=0.011048543456039804, attention_in_multiplier=1.0,
        attention_out_multiplier=0.0375, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=[0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738],
        mlp_multipliers=[0.1767766952966369, 0.011160714285714284],
        lm_head_multiplier=0.0078125),
}


def make_config(block: str, model=None, seq_length: int = 128,
                **sections) -> Config:
    """``block``'s toy with ``model``'s keys over it and the other sections
    as given, through ``Config.from_dict`` (which validates)."""
    return Config.from_dict({
        "distributed": {"use_cpu": True, **sections.pop("distributed", {})},
        "model": dict(TOYS[block], **(model or {})),
        "training": {"seq_length": seq_length},
        "dataset": {"name": "synthetic"}, **sections})
