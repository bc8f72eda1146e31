"""Share of the traced window in which no operation ran on the device, in
% (1 - busy / window, from the profiler's trace)."""
from bench import traces

read = traces.idle_percent
