"""Set-up seconds inside the program's imports (union of the program's
``import`` spans that ended before the window's ``train.fit`` began: the
package's own, ``cli.common``'s, and each third-party import the program wraps
where it first performs it; they nest, so a union)."""

from benchmarks import spans_setup


def read(ctx):
    return spans_setup.named_s("import")
