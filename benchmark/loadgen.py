"""Open-loop HTTP load: one process, one thread, asyncio.

Started by the serve driver as a child process (it shares no interpreter
with the runtime's head, so the generator's own scheduling cannot be read
as the server's).  Reads one JSON object from the file named on the command
line: where to send and the schedule (``due`` seconds relative to the window
start, ``prompts``, ``max_new``).  Having loaded it, it prints ``ready`` and
reads from its standard input when the window starts (wall clock), so that
its own start-up is over before that instant is fixed.
Every request is sent at its due time whether or not earlier ones have
finished, as a streaming POST on a connection of its own; every token's
arrival is timed.  Writes one JSON object of per-request records to the
output file.  All times are seconds relative to the window start.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time


async def one_request(spec, i, clock, records):
    rec = {"i": i, "due": spec["due"][i], "sent": None, "times": [],
           "tokens": [], "status": None, "error": None}
    records[i] = rec
    body = json.dumps({"tokens": spec["prompts"][i],
                       "max_new_tokens": spec["max_new"][i],
                       "stream": True}).encode()
    head = (f"POST {spec['path']} HTTP/1.1\r\nHost: {spec['host']}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n").encode()
    writer = None
    try:
        rec["sent"] = clock()
        reader, writer = await asyncio.open_connection(spec["host"], spec["port"])
        writer.write(head + body)
        await writer.drain()
        status = await reader.readuntil(b"\r\n\r\n")
        rec["status"] = int(status.split(b" ", 2)[1])
        if rec["status"] != 200 or b"chunked" not in status.lower():
            rec["error"] = status.split(b"\r\n", 1)[0].decode("latin-1")
            return
        pending = b""
        while True:
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                break
            data = await reader.readexactly(size + 2)
            now = clock()
            pending += data[:-2]
            *lines, pending = pending.split(b"\n")
            for line in lines:
                if line:
                    rec["tokens"].append(int(line))
                    rec["times"].append(now)
        rec["done"] = True
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def guarded(spec, i, clock, records):
    try:
        await asyncio.wait_for(one_request(spec, i, clock, records),
                               spec["timeout_s"])
    except asyncio.TimeoutError:
        records[i]["error"] = "client timeout"


async def main_async(spec) -> list:
    # the monotonic instant that is the window's start
    mono0 = time.monotonic() + (spec["t0_wall"] - time.time())
    clock = lambda: time.monotonic() - mono0
    records: list = [None] * len(spec["due"])
    tasks = []
    for i in sorted(range(len(spec["due"])), key=lambda j: spec["due"][j]):
        delay = spec["due"][i] - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(guarded(spec, i, clock, records)))
    await asyncio.gather(*tasks)
    return records


def main(argv) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    print("ready", flush=True)
    spec["t0_wall"] = float(sys.stdin.readline())
    records = asyncio.run(main_async(spec))
    with open(argv[2], "w") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
