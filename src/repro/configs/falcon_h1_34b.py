"""Falcon-H1-34B-Instruct — hybrid: GQA attention and Mamba-2 heads side by
side on one normed input in every layer, then a SwiGLU FFN
[huggingface.co/tiiuae/Falcon-H1-34B-Instruct, config.json].

The µP multipliers are the published ones: the block adds
attention_out × attention + ssm_out × SSM, the keys are scaled by
key_multiplier, the FFN's gate and output by mlp_multipliers, the
embedding by 5.657 and the logits by 1/128; the mixer scales its input by
ssm_in_multiplier and its in-projection's five parts by ssm_multipliers.
The Mamba-2 mixer has an inner width (mamba_d_ssm 4096) apart from
expand × d_model, B/C in two groups and its gated RMSNorm after the gate.
"""
from repro.configs.base import ModelConfig, Multipliers, SSMConfig

CONFIG = ModelConfig(
    name="falcon-h1-34b",
    arch_type="hybrid",
    num_layers=72,
    d_model=5120,
    num_heads=20,
    num_kv_heads=4,
    d_ff=21504,
    vocab_size=261120,
    head_dim=128,
    ssm=SSMConfig(d_state=256, head_dim=128, expand=2, chunk_size=128,
                  d_conv=4, n_groups=2, d_inner=4096, in_multiplier=0.25,
                  proj_multipliers=(0.3535533905932738, 0.25,
                                    0.1767766952966369, 0.5,
                                    0.3535533905932738)),
    multipliers=Multipliers(
        embedding=5.656854249492381, lm_head=0.0078125,
        key=0.011048543456039804, attention_in=1.0,
        attention_out=0.0375, ssm_out=0.08838834764831845,
        mlp_gate=0.1767766952966369, mlp_down=0.011160714285714284),
    rope_theta=1e11,
    norm_eps=1e-5,
    source="huggingface.co/tiiuae/Falcon-H1-34B-Instruct",
)
