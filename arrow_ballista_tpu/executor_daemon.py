"""Executor daemon: ``python -m arrow_ballista_tpu.executor_daemon``.

Parity: the ballista-executor binary (reference ballista/executor/src/
bin/main.rs + executor_process.rs — work_dir setup, scheduler connect with
retry, graceful SIGTERM shutdown draining in-flight tasks).
"""
from __future__ import annotations

import argparse
import logging
import os
import signal
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="arrow_ballista_tpu executor")
    ap.add_argument("--scheduler-host", default="127.0.0.1")
    ap.add_argument("--scheduler-port", type=int, default=50050)
    ap.add_argument("--bind-host", default="127.0.0.1")
    ap.add_argument("--bind-port", type=int, default=0)
    ap.add_argument("--external-host",
                    default=os.environ.get("BALLISTA_EXTERNAL_HOST") or None,
                    help="address advertised to peers for shuffle fetch "
                         "(env BALLISTA_EXTERNAL_HOST; defaults to bind "
                         "host, or hostname when 0.0.0.0)")
    ap.add_argument("--work-dir", default=None)
    ap.add_argument("--concurrent-tasks", type=int, default=4)
    ap.add_argument("--connect-timeout-s", type=float, default=30.0)
    ap.add_argument("--scheduling-policy", choices=["push", "pull"],
                    default="push")
    ap.add_argument("--flight-port", type=int, default=-1,
                    help="standard Arrow Flight data plane port "
                         "(0 = ephemeral, -1 = disabled)")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="observability HTTP port serving prometheus "
                         "/metrics and /health (0 = ephemeral, "
                         "-1 = disabled)")
    ap.add_argument("--profile-dir", default=None,
                    help="keep a jax.profiler session open around task "
                         "execution and write it here on shutdown and on "
                         "SIGUSR2 (device operations plus the executor's "
                         "task/operator/device_wait spans, one clock)")
    ap.add_argument("--log-level", default="INFO")
    ap.add_argument("--log-dir", default=None,
                    help="write rotating log files here instead of stderr")
    ap.add_argument("--log-file-name-prefix", default="executor")
    ap.add_argument("--log-rotation-policy", default="daily",
                    choices=["minutely", "hourly", "daily", "never"])
    ap.add_argument("--log-format", default=None, choices=["text", "json"],
                    help="log output format (default: BALLISTA_LOG_FORMAT "
                         "env or text; json = one object per line with "
                         "job/trace correlation fields)")
    args = ap.parse_args(argv)

    from .utils.logsetup import init_logging

    init_logging(args.log_level, args.log_dir, args.log_file_name_prefix,
                 args.log_rotation_policy, fmt=args.log_format)
    # native-crash forensics: a SIGSEGV in a daemon otherwise dies silently
    import faulthandler

    faulthandler.enable()

    import jax

    from .executor.server import ExecutorServer
    from .net import wire

    # the device this executor's stages run on: starting the backend here
    # makes a chip that cannot be had fail at start-up, before the executor
    # registers, and not in its first task
    dev = jax.devices()[0]

    # connect-with-retry (reference executor_process.rs:194-232)
    deadline = time.monotonic() + args.connect_timeout_s
    while True:
        try:
            wire.call(args.scheduler_host, args.scheduler_port, "ping", timeout=3.0)
            break
        except Exception as e:  # noqa: BLE001
            if time.monotonic() > deadline:
                raise SystemExit(f"cannot reach scheduler: {e}")
            time.sleep(0.5)

    server = ExecutorServer(
        args.scheduler_host, args.scheduler_port, args.bind_host,
        args.bind_port, args.work_dir, args.concurrent_tasks,
        external_host=args.external_host, policy=args.scheduling_policy,
        flight_port=args.flight_port, metrics_port=args.metrics_port,
        profile_dir=args.profile_dir)
    server.start()
    logging.info("executor %s on %s:%s (work_dir %s, device %s/%s x%d)",
                 server.metadata.executor_id, server.rpc.host, server.rpc.port,
                 server.work_dir, dev.platform, dev.device_kind,
                 len(jax.devices()))

    stop, write = [], []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    if server.profile is not None:
        signal.signal(signal.SIGUSR2, lambda *a: write.append(1))
    while not stop:
        time.sleep(0.5)
        if write:
            write.clear()
            server.profile.write()
            logging.info("profile written under %s", args.profile_dir)
    logging.info("executor draining %d tasks", server.executor.active_tasks())
    server.drain_and_stop()


if __name__ == "__main__":
    main()
