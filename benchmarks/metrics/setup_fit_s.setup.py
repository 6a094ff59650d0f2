"""Set-up seconds inside ``Trainer.fit`` (the program's ``train.fit`` spans
that ended before the window's began: the three check steps and the warm-up as
the Trainer saw them, with the trace, lowering and cache load of the step
inside the first)."""

from benchmarks import spans_setup


def read(ctx):
    return spans_setup.named_s("train.fit")
