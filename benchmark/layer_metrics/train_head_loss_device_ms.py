"""Device ms per step of the train program under ``atpu_head_loss``: the LM head
and cross-entropy, forward and (the tape keeps the scope) backward."""

from benchmark import span_readers


def read(ctx):
    return span_readers.train_scope_ms(ctx, "atpu_head_loss")
