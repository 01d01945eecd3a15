"""Backend compiles that ``jax.monitoring`` reported inside the window
(persistent-cache hits included); every program should be compiled in
set-up, so anything but 0 is a program compiling or warming in the window."""


def read(ctx):
    return ctx["compiles_in_window"]
