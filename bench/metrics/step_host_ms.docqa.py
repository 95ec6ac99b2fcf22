"""Host time per engine step: the mean ``serve.step`` span less the union of
the ``serve.device_wait`` spans inside it, over the traced stretch."""
from bench.metrics._spans import step_host_ms as read  # noqa: F401
