"""model step: share (%) of prefilled positions that were padding, over the window's finished requests:
``100 * (1 - sum(prompt_len - prefix_hit_tokens) / sum(prefill_bucket * wave_rows_padded / wave_rows))`` from
``usage.timings``: each request is charged its share of the program its wave rode (sequence bucket x batch
bucket over the wave's real rows; a chunked prompt, each of its chunks).  ``prefill_dev_ms_per_ktok`` divides
by submitted tokens and cannot see this.  ``None`` when no finished request carries ``timings``."""


def read(ctx):
    real = charged = 0.0
    for e in ctx["events"]:
        t = (e.get("usage") or {}).get("timings")
        if not (e["measured"] and not e.get("error") and "done" in e and t and t.get("wave_rows")):
            continue
        real += e["prompt_len"] - t["prefix_hit_tokens"]
        charged += t["prefill_bucket"] * t["wave_rows_padded"] / t["wave_rows"] * max(1, t["prefill_chunks"])
    return 100.0 * (1.0 - real / charged) if charged else None
