"""`serve.run()` called -> returned: controller, grant, replica spawn,
backend start, weights, engine construction."""
from benchmarks.layer_metrics._common import span_between


def read(facts):
    return span_between(facts, "serve_run_called", "serve_run_returned")
