"""Task worker runner — the `celery worker` analog."""

from __future__ import annotations

import logging
import time

logger = logging.getLogger(__name__)


def add_parser(sub):
    p = sub.add_parser("worker", help="run a task-queue worker (+ optional beat)")
    p.add_argument("--queues", default=None, help="comma-separated queue names")
    p.add_argument("--concurrency", type=int, default=2)
    p.add_argument("--beat", action="store_true", help="also run periodic schedule")
    p.add_argument(
        "--lease-s", type=float, default=300.0,
        help="lease duration; the executing worker heartbeats it (lease/3)",
    )
    p.add_argument(
        "--drain-s", type=float, default=30.0,
        help="graceful-drain deadline on shutdown (finish in-flight tasks)",
    )
    return p


def run(args) -> int:
    # take (or be refused) the device BEFORE leasing any task: tasks run on
    # worker threads, where a backend that cannot come up — another process
    # holds the chip — would surface as retried task failures, not as the
    # one clear error cli/main.py prints for it
    from ..utils.device import device_info, device_line

    logger.info("worker on %s", device_line(device_info()))
    # register all task modules
    from ..bot import tasks as bot_tasks  # noqa: F401
    from ..processing import signals, tasks as processing_tasks  # noqa: F401
    from ..tasks import Worker

    try:
        from ..broadcasting import tasks as broadcasting_tasks  # noqa: F401
    except ImportError:
        broadcasting_tasks = None

    # dead-letter / worker-loss events land in a crash-artifact trail like the
    # serving plane's; optional — a worker without the obs plane keeps running
    flight = None
    try:
        from ..serving.obs import FlightRecorder

        flight = FlightRecorder(name="task-worker")
    except Exception:
        logger.warning("serving.obs unavailable; no task flight recorder")

    queues = args.queues.split(",") if args.queues else None
    worker = Worker(
        queues, concurrency=args.concurrency, lease_s=args.lease_s, flight=flight
    ).start()
    worker.register_metrics()
    from ..tasks import Beat

    # ledger TTL maintenance rides the worker's beat — never the webhook
    # request path (the sweep is a delete over the created_at index)
    beat = Beat().add(bot_tasks.prune_ledgers_task, 3600.0)
    if args.beat and broadcasting_tasks is not None:
        beat.add(broadcasting_tasks.check_scheduled_broadcasts, 30.0)
    beat.start()
    print(f"worker started (queues={worker.queues}, concurrency={args.concurrency})")
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        print(f"draining (deadline {args.drain_s:g}s)...")
        if beat:
            beat.stop()
        clean = worker.drain(timeout_s=args.drain_s)
        worker.stop(timeout_s=1.0)
        stats = worker.stats()
        print(
            "stopped"
            + (" (drain deadline hit; leases will expire)" if not clean else "")
            + f": done={stats['done']} retries={stats['retries']} "
            f"dead_lettered={stats['dead_lettered']} reclaimed={stats['reclaimed_leases']}"
        )
    return 0
