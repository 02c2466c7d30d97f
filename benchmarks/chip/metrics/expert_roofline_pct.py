"""The grouped expert matmul's share of its roofline, in percent: the least
time the chip could take for the work, the larger of its operations over
the bf16 peak and its bytes over the HBM peak (``peaks.py``), over the
device time of the ``rafi.moe_experts`` scope (mean over chips) in the
traced window.

Per chip, over the traced jobs: operations are 6·D·F per assignment the
experts processed (the program's counter, never padded rows); bytes are the
held experts' weights once a layer (3·D·F bf16 each) plus each processed
row in and out (2·D bf16)."""
import jax

import peaks


def work(spec, processed, layers, chips):
    """``(flops, bytes)`` per chip of ``processed`` assignments over
    ``layers`` layers on ``chips`` chips."""
    D, F, e_loc = spec["hidden_size"], spec["moe_intermediate_size"], spec["n_routed_experts"]
    rows = processed / chips
    return 6.0 * D * F * rows, layers * e_loc * 3 * D * F * 2 + rows * 2 * D * 2


def share(flops, nbytes, seconds, peak):
    return 100.0 * max(flops / peak["bf16_flop_per_s"], nbytes / peak["hbm_bytes_per_s"]) / seconds


def read(run):
    if run.trace is None:
        return None
    seconds = run.trace.scopes.get("rafi.moe_experts")
    processed = sum(j.get("processed", 0) for j in run.traced)
    if not seconds or not processed:
        return None
    layers = sum(j["layers"] for j in run.traced)
    flops, nbytes = work(run.cell.spec, processed, layers, run.cell.chips)
    return share(flops, nbytes, seconds, peaks.lookup(jax.devices()[0].device_kind))
