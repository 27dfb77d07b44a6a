"""From the start of the process to the moment jax holds the chips: the
interpreter, the imports of jax and the TPU runtime's own start. It is not in
`setup_s` (PERF.md section 2 says why); it is reported beside it so that work
moved out of set-up to before that moment shows here."""


def read(ctx):
    return ctx.get("runtime_start_s")
