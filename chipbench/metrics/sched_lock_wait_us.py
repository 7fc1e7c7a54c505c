"""Mean over the tasks committed in the window of the time spent
acquiring the runtime lock before each decision on them (``lock_wait``
spans summed), in us."""
from chipbench import spans


def read(run):
    return spans.per_task_us(run, ("lock_wait",))
