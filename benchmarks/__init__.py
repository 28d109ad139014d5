"""The benchmark of paddle_tpu: cells, configurations, traffic, the reduction
from traces to metrics and the comparison that decides ``correct``.

Everything here is the yardstick; the program under test is reached only
through its public entry points (see README.md).
"""
