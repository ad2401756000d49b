"""Peak device memory of the fullest chip, GiB, from
``memory_stats()["peak_bytes_in_use"]`` read when the window closed."""


def read(ctx):
    b = ctx["system"]["peak_bytes"]
    return b / 2 ** 30 if b else None
