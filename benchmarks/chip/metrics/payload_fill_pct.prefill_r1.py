"""Useful share of the rows the payload pass carries in an expert-parallel
MoE cell on one chip: the reading of ``payload_fill_pct.prefill_r4.py``."""
from pathlib import Path

from harness import load_module

read = load_module(Path(__file__).with_name("payload_fill_pct.prefill_r4.py")).read
