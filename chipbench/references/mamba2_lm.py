"""Plain reference of the Mamba-2 language model as the benchmark runs it
(``state-spaces/mamba2-*``, ``mamba_ssm`` ``MambaLMHeadModel`` with
``Mamba2`` mixers and no MLP): the harness's interface over
``references/mamba2.py``, which holds the arithmetic.

Float32 at HIGHEST matmul precision, the SSD recurrence as a plain scan over
time, the published gated norm (``rmsnorm(y * silu(z)) * w``), a final
RMSNorm and the tied embedding.  It imports nothing of the system under
test.

Departure: the traffic draws token ids from all rows of the padded
embedding (50288), so the 11 padding rows past the tokenizer's 50277 are
possible tokens.
"""
from __future__ import annotations

from typing import Dict

from chipbench.references import mamba2
from chipbench.references.mamba2 import Dims, head, init_params, layers  # noqa: F401

# what the published mixer is; anything else is a different model
PUBLISHED_MIXER = {"layer": "Mamba2", "rmsnorm": True, "norm_before_gate": False,
                   "D_has_hdim": False, "bias": False, "conv_bias": True}

# every layer of the served stack: a Mamba-2 mixer and no MLP
LAYER = {"kind": "mamba", "ffn": False, "moe": False, "window": None}


def dims(config: Dict) -> Dims:
    """Sizes from the configuration file's source keys; refuses a
    configuration this reference does not compute."""
    ssm = config["ssm_cfg"]
    odd = {k: ssm.get(k) for k, v in PUBLISHED_MIXER.items() if ssm.get(k) != v}
    if odd:
        raise ValueError(f"this reference covers the published Mamba2 mixer, not {odd}")
    if config["d_intermediate"] or config["attn_layer_idx"]:
        raise ValueError("this reference covers Mamba-2 layers without MLP or attention")
    if not config["tie_embeddings"]:
        raise ValueError("this reference covers tied embeddings only")
    return mamba2.dims(config)


def program_fields(dm: Dims) -> Dict:
    """The served program's configuration fields these sizes fix; the
    harness refuses to run a program whose configuration differs."""
    return {
        "d_model": dm.d, "n_layers": dm.layers, "vocab_size": dm.vocab,
        "norm_eps": dm.eps, "ssm_state": dm.state, "ssm_head_dim": dm.head_dim,
        "ssm_expand": dm.inner // dm.d, "ssm_groups": dm.groups,
        "conv_kernel": dm.conv, "d_ff": 0, "tie_embeddings": True,
        "mamba_split_proj": False, "frontend": None,
    }


def flops(dm: Dims, prompt_len: int) -> float:
    """Model FLOPs of one prefill whose head scores the last position only
    (what a first token needs): 2 per multiply-add of ``in_proj`` and
    ``out_proj`` per token; the recurrence, 5 per state element per token
    (decay, input and add of the state update, and the multiply-add of
    ``C h``), not the chunked form's extra work; and the tied head once."""
    S = prompt_len
    zxbcdt = 2 * dm.inner + 2 * dm.groups * dm.state + dm.heads
    proj = dm.d * zxbcdt + dm.inner * dm.d
    scan = 5 * dm.heads * dm.head_dim * dm.state
    return float(dm.layers * (2 * proj + scan) * S + 2 * dm.vocab * dm.d)
