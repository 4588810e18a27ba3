"""Device ms per step of the train program under ``atpu_update`` (the optimizer)."""

from benchmark import span_readers


def read(ctx):
    return span_readers.train_scope_ms(ctx, "atpu_update")
