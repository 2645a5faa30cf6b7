"""model step: device time of one decode step (ms): the traced time of the fused decode-tick
program (``jit_tick``) over its runs times the steps a tick fuses.  Not listed for cells whose
prompts are longer than a chunk: there the piggyback program, which carries a prefill chunk, has the same name."""


def read(ctx):
    tr = ctx["trace"]
    if not tr:
        return None
    runs = tr["program_runs"].get("jit_tick")
    steps = ctx["c1"].get("decode_steps")
    if not runs or not steps:
        return None
    return tr["program_s"]["jit_tick"] * 1e3 / (runs * steps)
