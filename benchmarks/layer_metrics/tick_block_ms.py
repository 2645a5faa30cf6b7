"""engine: time the engine's thread waits for a tick's result (ms per tick), ``tick_stats()``
running average at the window's close (the program keeps no total to take a difference of)."""


def read(ctx):
    return ctx["c1"].get("tick_block_ms_avg")
