"""tick_host_ms.batch (ms; layer: engine tick, PagedEngine.step): mean
over the window's working ticks of the engine's own ``host_ms_trace``,
the tick's host time less its wait for the device's results (batch
cell)."""
from bench.record import tick_host_ms as read  # noqa: F401
