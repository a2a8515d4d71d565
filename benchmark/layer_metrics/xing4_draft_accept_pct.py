"""Share of the drafts put to the test that the model's own argmax
confirmed: the program's ``lm_draft_accepted`` over
``lm_draft_positions`` of the window's calls. On seeded weights the
module agrees with the model about once in a vocabulary's size, so this
reads ~0 here and a stream advances one token a step; a trained
module's 80-90% would end a call in about 1 / 1.85 of the steps."""

from benchmark.layer_metrics import _xing4

DRIVERS = _xing4.DRIVERS


def read(record):
    calls = _xing4.window_calls(record)
    tried = sum(c["draft_positions"] for c in calls)
    return (100.0 * sum(c["draft_accepted"] for c in calls) / tried
            if tried else None)
