"""compiles_in_window: executables JAX built inside the measured window,
backend compiles and loads from the persistent compilation cache alike
(JAX's monitoring events, counted by the harness across the window).
The engine's ``ExecutableCache`` builds go through the same path, so its
compiles are among them; so are the small programs of eager operations
on a shape not seen in warm-up.  0 when warm-up reached every shape."""


def read(ctx):
    return ctx["window"].compiles
