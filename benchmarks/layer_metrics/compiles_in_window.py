"""engine: programs JAX compiled or fetched inside the window (count of JAX's
backend-compile events).  Anything but 0 means a shape was not warmed."""


def read(ctx):
    return float(ctx["compiles_in_window"])
