"""Device ms per decode step under ``atpu_serve_qkv`` + ``atpu_serve_out_mlp`` +
``atpu_serve_head``: the phases that read the weights."""

from benchmark import span_readers


def read(ctx):
    return span_readers.decode_group_ms(ctx, "weights")
