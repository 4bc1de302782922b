"""The decode-attention kernels' share of their roofline (memory bandwidth is the bound):
``rooflines/decode_attention.py`` over ``fused_paged_decode_attention`` +
``fused_decode_attention`` time."""

from benchmark.trace import serving

read = serving.decode_attention_roofline_pct
