# Pallas TPU kernels for the perf-critical compute layers:
#   deconv    — the paper's hardware-aware transposed conv (phase-decomposed)
#   attention — flash attention (GQA/causal/window/softcap)
#   ssd       — Mamba-2 chunked state-space scan
# Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit wrapper
# with XLA fallback), ref.py (pure-jnp oracle). backend.py decides interpret
# mode from the platform: compiled on TPU, interpreted elsewhere.
from .deconv.ops import deconv2d
from .attention.ops import attention as flash_attention_op
from .ssd.ops import ssd as ssd_op
