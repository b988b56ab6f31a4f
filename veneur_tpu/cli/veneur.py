"""The main daemon CLI — `python -m veneur_tpu.cli.veneur -f config.yaml`.

Parity: cmd/veneur/main.go (sym: main): read config, build server, run
until signalled.
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading


def main(argv=None):
    ap = argparse.ArgumentParser(prog="veneur-tpu")
    ap.add_argument("-f", dest="config", required=True,
                    help="path to YAML config")
    ap.add_argument("--validate-config", action="store_true",
                    help="parse config and exit")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="enable debug logging")
    args = ap.parse_args(argv)

    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")

    from ..config import read_config
    cfg = read_config(args.config)
    if args.validate_config:
        print("config ok")
        return 0

    from ..utils import platform
    if cfg.aggregation_backend == "cpu":
        platform.pin_cpu()
    else:
        # libtpu without a chip makes JAX warn and hand back the CPU;
        # a daemon configured for the TPU must not serve on that
        platform.require_tpu(
            f"aggregation_backend: {cfg.aggregation_backend}")
    platform.setup_compile_cache()

    from ..server import Server
    srv = Server(cfg)
    srv.start()
    logging.getLogger("veneur").info(
        "veneur-tpu serving: statsd=%s interval=%ss workers=%d",
        cfg.statsd_listen_addresses, cfg.interval_seconds, cfg.num_workers)

    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            signal.signal(sig, lambda *_: stop.set())
        except ValueError:
            # not the main thread (embedded/test use): rely on the
            # caller to stop us instead of signals
            break
    stop.wait()
    srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
