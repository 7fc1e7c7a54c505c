"""Share of the run's model decode steps whose input state the step
consumed, so that it wrote its cache in place: the engine's
``decode_in_place`` over its ``decode_steps`` (program counters, over the
whole run, set-up included).  1.0 where every step writes in place."""


def read(run):
    c = run.counters or {}
    steps = c.get("decode_steps", 0)
    return c["decode_in_place"] / steps if steps else None
