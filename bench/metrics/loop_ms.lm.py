"""Host round loop of the LM cell: self time of ``round.total`` not
covered by the step, chain and eval spans, per round (ms)."""
from bench.readers import loop_self_ms


def read(layer):
    return loop_self_ms(layer, "round.total",
                        ("round.step", "round.chain", "round.eval"))
