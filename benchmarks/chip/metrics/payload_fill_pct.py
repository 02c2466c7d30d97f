"""Useful share of the rows the payload pass carries, in percent: deliveries
over the rows every forward of the window's jobs moves on all ranks,
``100 · Σ deliveries / (Σ (rounds + 1) · ranks · rows_per_forward)``.  A
job of ``rounds`` body rounds runs ``rounds + 1`` forwards (the seeding
forward first).  ``rows_per_forward`` is the gauge
``rafi_payload_rows_per_forward`` the program declares while it is traced
(``repro.obs.metrics.REGISTRY``); with no such gauge there is nothing to
read."""


def read(run):
    from repro.obs import metrics

    registry = getattr(metrics, "REGISTRY", None)  # None before the gauge existed
    rows = registry.get("rafi_payload_rows_per_forward") if registry is not None else None
    if not rows:
        return None
    forwards = sum(j["rounds"] + 1 for j in run.jobs)
    return 100.0 * sum(j["deliveries"] for j in run.jobs) / (forwards * run.cell.chips * rows)
