"""Mean live rows per call of ``model.decode_batch``, counted by the
benchmark's wrapper around it."""

NAME, UNIT, LAYER, MOVES = "decode_rows_mean.serve", "rows", "serving", \
    "serve_tokens_per_s"
KINDS = ("serve",)
SOURCE = "program_counter"


def read(trace, host, cell):
    calls = host.get("decode_calls", 0)
    return host["decode_rows"] / calls if calls else None
