"""Name the device this process computes on.

Nothing in the package may serve on "whatever backend came up" without saying
which one it was: ``serve`` logs :func:`device_info` at boot, ``/healthz``
carries it, and ``chip_smoke.py`` refuses anything but ``tpu`` from it.
"""

from __future__ import annotations

from typing import Dict, Optional, Union

ONE_PROCESS_PER_CHIP = """\
{who}: JAX could not initialise its backend:
    {error}
A TPU chip belongs to ONE process at a time.  When another process of this
deployment (`serve`, `api`, a `worker`, a test run) already holds the chip,
libtpu refuses the second one with the message above.  If it advises deleting
/tmp/libtpu_lockfile, don't: the lock is held, not stale.
What fits one chip:
  * everything in one process: the in-process `tpu:` provider
    (DABT_TPU_SERVING_CONFIG) puts engines and vector indexes together; or
  * `serve` alone on the chip, and every other process started with
    JAX_PLATFORMS=cpu, reaching the models over HTTP with the `gpu_service:`
    provider (their vector indexes then live on the CPU).
Anything else needs one chip per device-using process (README, "Processes and
chips")."""


def device_info() -> Dict[str, Union[str, int]]:
    """``{"platform", "kind", "count"}`` exactly as JAX reports them.

    Initialises the JAX backend (and so takes the chip, on a TPU host: a chip
    belongs to one process at a time)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def device_line(info: Dict[str, Union[str, int]]) -> str:
    """``platform=... device_kind=... count=...`` — the one-line form for logs."""
    return "platform={platform} device_kind={kind} count={count}".format(**info)


def explain_backend_failure(who: str, error: BaseException) -> Optional[str]:
    """The one-process-per-chip explanation when ``error`` is JAX failing to
    bring up its backend, else None (some other RuntimeError: re-raise it)."""
    text = str(error)
    if "Unable to initialize backend" not in text:
        return None
    return ONE_PROCESS_PER_CHIP.format(who=who, error=text.splitlines()[0])
