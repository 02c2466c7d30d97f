"""Useful share of the rows the payload pass carries in an expert-parallel
MoE cell, in percent.  Each layer of a job runs two trips (dispatch and
return); a trip of ``n`` body rounds runs ``n + 1`` forwards, so a job of
``rounds`` body rounds over ``layers`` layers runs ``rounds + 2 · layers``
forwards, and each delivered assignment rides once out and once back:
``100 · Σ 2 · deliveries / (Σ (rounds + 2 · layers) · ranks ·
rows_per_forward)``.  ``rows_per_forward`` is the gauge
``rafi_payload_rows_per_forward`` the program declares while it is traced
(``repro.obs.metrics.REGISTRY``); with no such gauge there is nothing to
read."""


def read(run):
    from repro.obs import metrics

    registry = getattr(metrics, "REGISTRY", None)
    rows = registry.get("rafi_payload_rows_per_forward") if registry is not None else None
    if not rows:
        return None
    forwards = sum(j["rounds"] + 2 * j["layers"] for j in run.jobs)
    carried = sum(2 * j["deliveries"] for j in run.jobs)
    return 100.0 * carried / (forwards * run.cell.chips * rows)
