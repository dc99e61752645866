"""Scheduler daemon: ``python -m arrow_ballista_tpu.scheduler_daemon``.

Parity: the ballista-scheduler binary (reference ballista/scheduler/src/
bin/main.rs + scheduler_process.rs — single-port server hosting the gRPC
surface; the configure_me TOML spec maps to argparse flags here).
"""
from __future__ import annotations

import argparse
import logging
import signal
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="arrow_ballista_tpu scheduler")
    ap.add_argument("--bind-host", default="0.0.0.0")
    ap.add_argument("--bind-port", type=int, default=50050)
    ap.add_argument("--rest-port", type=int, default=50051,
                    help="HTTP REST API port (-1 disables)")
    ap.add_argument("--flight-port", type=int, default=-1,
                    help="Arrow Flight (SQL) port (-1 disables; 0 = any). "
                         "JDBC-class Flight SQL clients and stock "
                         "pyarrow.flight clients connect here")
    ap.add_argument("--state-dir", default=None,
                    help="persist job graphs here for crash recovery / "
                         "multi-scheduler adoption")
    ap.add_argument("--cluster-backend", default=None, metavar="URL",
                    help="shared cluster-state store for HA multi-scheduler "
                         "deployments: memory:// or sqlite:///path/state.db "
                         "(reference: sled/etcd cluster backends)")
    ap.add_argument("--task-distribution", choices=["bias", "round-robin"],
                    default="bias")
    ap.add_argument("--scheduling-policy", choices=["push", "pull"],
                    default="push")
    ap.add_argument("--executor-timeout-s", type=float, default=180.0)
    ap.add_argument("--job-data-cleanup-delay-s", type=float, default=30.0,
                    help="delay before finished jobs' shuffle data is "
                         "removed from executors (<0 disables; the "
                         "executor TTL janitor remains as backstop)")
    ap.add_argument("--shuffle-partitions", type=int, default=16)
    ap.add_argument("--log-level", default="INFO")
    ap.add_argument("--log-dir", default=None,
                    help="write rotating log files here instead of stderr")
    ap.add_argument("--log-file-name-prefix", default="scheduler")
    ap.add_argument("--log-rotation-policy", default="daily",
                    choices=["minutely", "hourly", "daily", "never"])
    ap.add_argument("--log-format", default=None, choices=["text", "json"],
                    help="log output format (default: BALLISTA_LOG_FORMAT "
                         "env or text; json = one object per line with "
                         "job/trace correlation fields)")
    args = ap.parse_args(argv)

    # the scheduler plans and tracks; it runs no stage.  The chip belongs
    # to the executor process, so this one stays on the CPU platform.
    from .models.batch import pin_to_host

    pin_to_host()

    from .utils.logsetup import init_logging

    init_logging(args.log_level, args.log_dir, args.log_file_name_prefix,
                 args.log_rotation_policy, fmt=args.log_format)
    # native-crash forensics: a SIGSEGV in a daemon otherwise dies silently
    import faulthandler

    faulthandler.enable()

    import jax

    from .scheduler.netservice import SchedulerNetService
    from .scheduler.scheduler import SchedulerConfig
    from .utils.config import BallistaConfig

    svc = SchedulerNetService(
        args.bind_host, args.bind_port,
        config=BallistaConfig(
            {"ballista.shuffle.partitions": str(args.shuffle_partitions)}),
        scheduler_config=SchedulerConfig(
            task_distribution=args.task_distribution,
            executor_timeout_s=args.executor_timeout_s,
            policy=args.scheduling_policy,
            job_data_cleanup_delay_s=args.job_data_cleanup_delay_s),
        rest_port=None if args.rest_port < 0 else args.rest_port,
        state_dir=args.state_dir,
        cluster_url=args.cluster_backend,
        flight_port=None if args.flight_port < 0 else args.flight_port)
    svc.start()
    logging.info("scheduler listening on %s:%s (rest: %s, jax platforms: %s)",
                 svc.host, svc.port,
                 svc.rest.port if svc.rest else "disabled",
                 jax.config.jax_platforms)

    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    while not stop:
        time.sleep(0.5)
    logging.info("scheduler shutting down")
    svc.stop()


if __name__ == "__main__":
    main()
