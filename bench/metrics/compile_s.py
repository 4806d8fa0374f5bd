"""compile_s: seconds to lower and compile the cell's train step in
set-up, on the host clock (a load from the persistent cache after the
first run in a checkout).  Moves setup_s."""


def read(ctx):
    return ctx["compile_s"]
