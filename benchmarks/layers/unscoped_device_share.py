"""model + kernels: share of the traced window the chips spent in ops of
the step program that stand under no ``ddl.`` scope and are no kernel —
the instrument's own coverage: XLA's own copies carry no path, the loop's
bookkeeping no scope.  With the five scope shares and the kernels
it adds up to the step programs' own time.  Mean over the chips.  ``None``
as ``attn_dense_device_share`` has it."""

from benchmarks.lib import scopes


def read(m: dict):
    return scopes.share(m, lambda table: table.unscoped_s())
