"""HTTP serving smoke client: start the server, POST two staggered
requests, show that their token chunks interleave.

Spawns ``repro.launch.serve --http`` as a subprocess (or targets an
already-running server via --port), streams two /generate requests whose
arrivals are staggered, prints every NDJSON chunk as it lands, and — with
--assert-interleaved (the CI async-serving job) — exits nonzero unless the
late request's first chunk arrived before the early request's last one,
i.e. unless admission really is open mid-flight.

Run:  PYTHONPATH=src python examples/serve_http_client.py
      PYTHONPATH=src python examples/serve_http_client.py \
          --assert-interleaved --stagger 0.5
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_for_server(port: int, timeout: float = 300.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=2):
                return
        except OSError:
            time.sleep(0.25)
    raise SystemExit(f"server on port {port} never came up")


def stream_generate(port: int, payload: dict, tag: str, record: list,
                    lock: threading.Lock) -> None:
    """POST /generate and append (time, tag, chunk) per NDJSON line AS IT
    ARRIVES; the server closes the connection after the terminal line."""
    body = json.dumps(payload).encode()
    with socket.create_connection(("127.0.0.1", port), timeout=300) as s:
        s.sendall(b"POST /generate HTTP/1.1\r\nHost: smoke\r\n"
                  b"Content-Length: %d\r\n\r\n" % len(body) + body)
        f = s.makefile("rb")
        status = f.readline().decode().strip()
        if "200" not in status:
            raise SystemExit(f"{tag}: unexpected status {status}")
        while f.readline() not in (b"\r\n", b"\n", b""):
            pass
        for raw in f:
            raw = raw.strip()
            if not raw:
                continue
            chunk = json.loads(raw)
            with lock:
                record.append((time.monotonic(), tag, chunk))
                print(f"  [{tag}] token={chunk['token']} "
                      f"index={chunk['index']}"
                      + (f" finish={chunk['finish_reason']}"
                         if "finish_reason" in chunk else ""))


def get_stats(port: int) -> dict:
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        s.sendall(b"GET /stats HTTP/1.1\r\nHost: smoke\r\n\r\n")
        raw = b""
        while chunk := s.recv(65536):
            raw += chunk
    return json.loads(raw.split(b"\r\n\r\n", 1)[1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--port", type=int, default=0,
                    help="target an already-running server (0 = spawn one)")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--stagger", type=float, default=0.5,
                    help="seconds between the two POSTs")
    ap.add_argument("--assert-interleaved", action="store_true",
                    help="exit nonzero unless the late request streamed "
                         "before the early one finished")
    args = ap.parse_args()

    proc = None
    port = args.port
    if not port:
        port = free_port()
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.serve", "--arch", args.arch,
             "--reduce", "--http", str(port), "--max-len",
             str(args.prompt_len + args.max_new)],
            env=env, cwd=REPO)
    try:
        wait_for_server(port)
        record: list = []
        lock = threading.Lock()
        early = {"prompt": list(range(1, args.prompt_len + 1)),
                 "max_new": args.max_new}
        late = {"prompt": list(range(1, max(2, args.prompt_len // 2))),
                "max_new": max(2, args.max_new // 4)}
        print(f"POST /generate x2, staggered {args.stagger}s:")
        t1 = threading.Thread(target=stream_generate,
                              args=(port, early, "early", record, lock))
        t1.start()
        time.sleep(args.stagger)
        t2 = threading.Thread(target=stream_generate,
                              args=(port, late, "late", record, lock))
        t2.start()
        t1.join()
        t2.join()

        late_first = min(t for t, tag, _ in record if tag == "late")
        early_last = max(t for t, tag, _ in record if tag == "early")
        interleaved = late_first < early_last
        print(f"late request's first chunk {'BEFORE' if interleaved else 'after'} "
              "the early request's last chunk")
        stats = get_stats(port)
        print("stats:", json.dumps(stats, indent=2)[:400])
        if stats["engine"]["decode_compile_count"] not in (None, 1):
            raise SystemExit("decode recompiled across the mid-flight arrival")
        if args.assert_interleaved and not interleaved:
            raise SystemExit("chunks did not interleave: the late request "
                             "waited for the early one (closed batch?)")
    finally:
        if proc is not None:
            proc.terminate()
            proc.wait(timeout=30)


if __name__ == "__main__":
    main()
