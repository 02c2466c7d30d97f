"""The observation law (ISSUE 10): every law's behavior is observable from
one artifact, at zero collective cost.

Three pieces, beside the ``rafi.*`` device scopes the traced program
carries (``jax.named_scope`` at each layer boundary of ``repro.core``):

* :mod:`repro.obs.trace` — host-side span tracer over every drive entry
  point, each span also a ``rafi.*`` profiler annotation; Chrome/Perfetto
  ``trace_event`` export; ``RAFI_TRACE`` env toggle.
* :mod:`repro.obs.metrics` — typed counter/gauge snapshots per burst from
  already-surfaced telemetry, and the registry of gauges the program
  declares while traced; Prometheus text + JSON exporters.
* :mod:`repro.obs.report` — the flight-data analyzer
  (``python -m repro.obs.report capture.json``).

``trace`` and ``metrics`` import eagerly (no ``repro.core`` import — core
modules record into them without cycles); ``report`` loads lazily on first
attribute access.
"""
from repro.obs import metrics, trace

__all__ = ["metrics", "report", "trace"]


def __getattr__(name):
    if name == "report":
        import importlib

        mod = importlib.import_module(f"repro.obs.{name}")
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
