"""A reference that declares a Mamba2 with ``mamba_ngroups`` groups of B
and C (pattern ``["mamba"]``, tied embeddings): its widths only, for the
harness's set-up check; it computes no forward pass."""


def param_shapes(sizes):
    d, V, G = sizes["d_model"], sizes["vocab"], sizes["mamba_ngroups"]
    di = sizes["mamba_expand"] * d
    N, K = sizes["ssm_state"], sizes["mamba_d_conv"]
    H = di // sizes["mamba_headdim"]
    r = sizes["n_layers"]
    mamba = {"in_proj": {"w": (r, d, 2 * di + 2 * G * N + H)},
             "conv_w": (r, K, di + 2 * G * N), "conv_b": (r, di + 2 * G * N),
             "dt_bias": (r, H), "A_log": (r, H), "D": (r, H),
             "norm": {"scale": (r, di)}, "out_proj": {"w": (r, di, d)}}
    return {"emb": (V, d), "final_norm": {"scale": (d,)},
            "units": ({"norm1": {"scale": (r, d)}, "mamba": mamba},)}
