"""mamba2-780m — SSD (state-space duality), attention-free [arXiv:2405.21060; hf:state-spaces/mamba2-780m]."""
from repro.configs.base import LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="mamba2-780m",
    family="ssm",
    n_layers=48,
    d_model=1536,
    n_heads=0,  # attention-free
    n_kv_heads=0,
    d_ff=0,  # no FFN: mamba2 blocks only
    vocab_size=50288,  # 50277 padded to a multiple of 16
    pattern=(LayerSpec(kind="mamba", ffn=False),),
    pattern_reps=48,
    # the mixer's sizes are the mamba_ssm Mamba2 class defaults: the
    # published config names only the layer class
    ssm_state=128,
    ssm_head_dim=64,
    ssm_expand=2,
    tie_embeddings=True,
    norm_eps=1e-5,
    long_context_ok=True,  # O(1) recurrent state
    source="https://huggingface.co/state-spaces/mamba2-780m/blob/main/config.json",
)
