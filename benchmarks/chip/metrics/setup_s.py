"""setup_s: seconds from the command's start to the window's start.

Imports, data generation, planning (the host symbolic phase), the upload
of the stream's indices, and compiling or loading every executable the
window runs.
"""


def read(ctx):
    return ctx.setup_s
