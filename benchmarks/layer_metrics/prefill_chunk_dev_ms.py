"""model step: device time of one prefill chunk program (ms): the traced time of every program with
``prefill_chunk`` in its name (one shape a configuration: 1 x ``chunk_size`` against the slot's pages) over
its runs.  Where prompts are longer than a chunk and ``prefill_piggyback`` is off, nearly every prefill
program is one of these.  A run the traced span cuts at an edge counts whole, its time in part."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    runs = sum(n for name, n in tr["program_runs"].items() if "prefill_chunk" in name)
    t = sum(s for name, s in tr["program_s"].items() if "prefill_chunk" in name)
    return t * 1e3 / runs if runs and t else None
