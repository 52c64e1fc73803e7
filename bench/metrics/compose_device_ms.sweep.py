"""compose_device_ms.sweep (ms/sweep): device time of the jitted
composition program (``batch_jax._compose_fn``, an XLA module named
``jit_compose``) in the traced window, per sweep completed there."""


def read(facts: dict, trace):
    if trace is None:
        return None
    sweeps = trace.spans.get("sweep", 0)
    t = trace.module_seconds("jit_compose")
    if not sweeps or not t:
        return None
    return 1000.0 * t / sweeps
