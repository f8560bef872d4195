"""The program's ``ArchConfig`` for a Mamba-2 configuration file."""
from __future__ import annotations

# What the program's Mamba-2 block runs; any other value is refused.
FIXED = {"architecture": "mamba2", "conv_bias": False,
         "residual_in_fp32": False, "compute_dtype": "bfloat16",
         "param_dtype": "float32", "tie_embeddings": True}


def arch_config(c: dict):
    from repro.configs.base import ArchConfig
    from repro.models.ssm import SSMCfg

    for k, v in FIXED.items():
        if c.get(k) != v:
            raise ValueError(f"{c['name']}: the program runs {k}={v} only")
    return ArchConfig(
        name=c["name"], family="ssm", num_layers=c["n_layer"],
        d_model=c["d_model"], num_heads=1, num_kv_heads=1,
        head_dim=c["headdim"], d_ff=0, vocab_size=c["vocab_size"],
        tie_embeddings=True, sub_quadratic=True,
        ssm=SSMCfg(d_state=c["d_state"], head_dim=c["headdim"],
                   expand=c["expand"], n_groups=c["ngroups"],
                   conv_width=c["d_conv"], chunk=c["chunk_size"]))
