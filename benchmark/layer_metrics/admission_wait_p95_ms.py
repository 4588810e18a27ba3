"""p95 of admitted - submitted over the requests submitted in the untraced
part, from the program's own stamps (``Request.admitted_t`` on the flight
recorder's ring).  ``queue_wait_p95_ms`` derives the same wait from outside."""

from benchmark import span_readers


def read(ctx):
    return span_readers.admission_wait_p95_ms(ctx)
