"""Set-up seconds that no program span covers: from the start of the
process's first program span (the package's ``import``) to the start of the
window's ``train.fit``, less the union of EVERY program span that ended in
between, whatever its name. Says whether the start-up timeline is whole: work
added to set-up without a span raises it."""

from benchmarks import spans_setup


def read(ctx):
    return spans_setup.unnamed_s()
