"""Set-up seconds inside ``Trainer.__init__`` (the program's ``trainer.init``
span(s) that ended before the window's ``train.fit`` began)."""

from benchmarks import spans


def read(ctx):
    return spans.setup_union_s("trainer.init")
