"""The state-update kernel's share of its roofline (memory bandwidth is the bound) in the
``M`` layers alone: ``rooflines/nemotron_ssm_decode_update.py`` over ``ssm_decode_update``
time in the traced ticks."""

from benchmark.trace import nemotron

read = nemotron.ssm_update_roofline_pct
