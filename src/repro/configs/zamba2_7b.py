"""Zamba2-7B [arXiv:2411.15242; hf Zyphra/Zamba2-7B-Instruct config.json].

81 layers d_model=3584: grouped Mamba2 in every layer (112 heads of 64,
d_state 64, 2 groups of B and C, conv 4) and, at the 13 ``hybrid`` layers,
one of two shared attention + FFN blocks used in turn.  A block reads
concat(x, token embeddings) (7168 wide): 32 heads of 224 with RoPE over
all 224 dims and the softmax scaled by (224 / 2) ** -0.5, then a gated
exact-GELU FFN of 14336 with a per-use LoRA of rank 128 on its gate/up
projection; a per-use linear maps the block's output, which is added to
that layer's Mamba2 input (not to the residual stream).  Tied embeddings
(`Zamba2Config` leaves ``tie_word_embeddings`` at its default, true).
"""
from repro.configs.base import ArchConfig, register

#: the published config.json, the keys this program reads
PUBLISHED = {
    "num_hidden_layers": 81, "hidden_size": 3584, "vocab_size": 32000,
    "num_attention_heads": 32, "num_key_value_heads": 32,
    "attention_head_dim": 224, "intermediate_size": 14336,
    "hidden_act": "gelu", "rope_theta": 10000, "use_mem_rope": True,
    "layers_block_type": (["mamba"] * 6 + ["hybrid"] + ["mamba"] * 4 +
                          ["hybrid"] + (["mamba"] * 5 + ["hybrid"]) * 11 +
                          ["mamba"] * 3),
    "mamba_d_state": 64, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_headdim": 64, "mamba_ngroups": 2, "use_conv_bias": True,
    "num_mem_blocks": 2, "adapter_rank": 128, "use_shared_mlp_adapter": True,
    "use_shared_attention_adapter": False, "use_long_context": False,
    "add_bias_linear": False, "time_step_limit": None,
}

#: published options and the one value of each this program computes
_COMPUTED = {"use_mem_rope": True, "use_conv_bias": True,
             "use_shared_mlp_adapter": True,
             "use_shared_attention_adapter": False, "use_long_context": False,
             "add_bias_linear": False, "time_step_limit": None,
             "mamba_expand": 2, "mamba_headdim": 64, "mamba_d_conv": 4,
             "hidden_act": "gelu"}


def from_published(c: dict, name: str = "zamba2-7b") -> ArchConfig:
    """The ArchConfig of a Zamba2 ``config.json`` (keys as published).
    Options the program does not compute (attention adapters, long-context
    RoPE, biases, a dt limit, other Mamba2 shapes) are refused."""
    for key, value in _COMPUTED.items():
        if c.get(key, value) != value:
            raise ValueError(f"Zamba2 option {key}={c[key]!r} is not "
                             f"supported (this program computes "
                             f"{key}={value!r})")
    hd = c["attention_head_dim"]
    return ArchConfig(
        name=name, family="hybrid", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=hd,
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        norm="rmsnorm", act="gelu_exact", gated_ffn=True,
        rope_theta=float(c["rope_theta"]), tie_embeddings=True,
        pattern=tuple(c["layers_block_type"]), ssm_state=c["mamba_d_state"],
        mamba_ngroups=c["mamba_ngroups"], mem_blocks=c["num_mem_blocks"],
        adapter_rank=c["adapter_rank"], attn_scale=(hd / 2) ** -0.5)


CONFIG = register(from_published(PUBLISHED))
