"""Mean host time of an ``Engine.step()`` that did work spent blocked on
the device: the ``engine.fetch`` spans (each a blocking read of logits)
under each ``engine.step`` span, over the window less its traced slice."""

from bench.metrics.sample_ms import per_tick_ms


def read(rec):
    return per_tick_ms(rec, "engine.fetch")
