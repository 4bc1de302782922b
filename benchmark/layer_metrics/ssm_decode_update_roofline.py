"""The state-update kernel's share of its roofline (memory bandwidth is the bound):
``rooflines/ssm_decode_update.py`` over ``ssm_decode_update`` time in the traced ticks."""

from benchmark.trace import recurrent

read = recurrent.ssm_update_roofline_pct
