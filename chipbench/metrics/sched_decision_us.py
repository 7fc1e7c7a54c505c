"""Mean over the tasks committed in the window of the time the runtime
spent, with its lock held, in the scheduling kernel's work for each:
its ``wake``, ``place`` and ``commit`` spans summed, in us."""
from chipbench import spans


def read(run):
    return spans.per_task_us(run, spans.DECISION)
