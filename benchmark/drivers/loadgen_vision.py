"""Open-loop HTTP load whose requests carry a VIDEO: one process, asyncio.

``loadgen.py``'s protocol and records (read a JSON spec from the file named on
the command line, print ``ready``, read the window's start from standard
input, send every request at its due time on a connection of its own whether
or not earlier ones have finished, time every streamed token, write the
records), with a request body of its own kind::

    {"tokens": [...], "max_new_tokens": n, "stream": true,
     "video": {"grid": [F, gh, gw], "patches": <base64 of uint8 [F x gh x gw, values]>}}

A body is tens of megabytes (a median video of 80 frames of 16 x 16 patches is
12 MB of pixels, 16 MB in base64), so it is BUILT BEFORE IT IS DUE
(``lead_s`` ahead, on a worker thread: the pixels drawn from the seed, encoded,
the JSON assembled) and only sent at its due time; the records then hold
nothing of it.  The pixels of request ``i`` are :func:`pixels` of the spec's
seed: uniform random bytes, the same for the driver's reference check.
"""

from __future__ import annotations

import asyncio
import base64
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

LEAD_S = 2.0  # a body is ready this long before it is due
PIECE = 3 << 16  # bytes of pixels encoded at a time


def pixels(seed: int, i: int, n_patches: int, values: int) -> np.ndarray:
    """Request ``i``'s patches, ``[n_patches, values]`` uint8 from the seed."""
    return np.random.default_rng([int(seed), 1_000_003, int(i)]).integers(
        0, 256, (n_patches, values), dtype=np.uint8)


def body_of(spec, i) -> bytes:
    frames, (gh, gw) = spec["frames"][i], spec["frame_grid"]
    head = json.dumps({"tokens": spec["prompts"][i],
                       "max_new_tokens": spec["max_new"][i], "stream": True})
    if not frames:
        return head.encode()
    # encoded a piece at a time (whole multiples of 3 bytes): ONE b64encode
    # over 36 MB holds the interpreter lock for a tenth of a second, during
    # which the event loop stamps no token's arrival
    raw = memoryview(pixels(
        spec["seed"], i, frames * gh * gw, spec["patch_values"])).cast("B")
    pieces = [base64.b64encode(raw[lo:lo + PIECE])
              for lo in range(0, len(raw), PIECE)]
    return b"".join([
        head[:-1].encode(), b', "video": {"grid": ',
        json.dumps([frames, gh, gw]).encode(), b', "patches": "', *pieces,
        b'"}}'])


async def one_request(spec, i, clock, records, body_ready):
    rec = {"i": i, "due": spec["due"][i], "sent": None, "times": [],
           "tokens": [], "status": None, "error": None, "body_bytes": None}
    records[i] = rec
    writer = None
    try:
        body = await body_ready
        rec["body_bytes"] = len(body)
        delay = spec["due"][i] - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        head = (f"POST {spec['path']} HTTP/1.1\r\nHost: {spec['host']}\r\n"
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n").encode()
        rec["sent"] = clock()
        reader, writer = await asyncio.open_connection(spec["host"], spec["port"])
        writer.write(head)
        writer.write(body)
        del body
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
        if rec["sent"] is None:
            rec["sent"] = clock()
    finally:
        if writer is not None:
            writer.close()


async def guarded(spec, i, clock, records, body_ready):
    try:
        # the client's timeout runs from the request's due time
        await asyncio.wait_for(
            one_request(spec, i, clock, records, body_ready),
            max(0.0, spec["due"][i] - clock()) + spec["timeout_s"])
    except asyncio.TimeoutError:
        records[i]["error"] = "client timeout"
        if records[i]["sent"] is None:
            records[i]["sent"] = clock()


async def main_async(spec) -> list:
    mono0 = time.monotonic() + (spec["t0_wall"] - time.time())
    clock = lambda: time.monotonic() - mono0
    loop = asyncio.get_running_loop()
    pool = ThreadPoolExecutor(2, thread_name_prefix="body")
    records: list = [None] * len(spec["due"])
    tasks = []
    for i in sorted(range(len(spec["due"])), key=lambda j: spec["due"][j]):
        delay = spec["due"][i] - LEAD_S - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        ready = loop.run_in_executor(pool, body_of, spec, i)
        tasks.append(asyncio.ensure_future(
            guarded(spec, i, clock, records, ready)))
    await asyncio.gather(*tasks)
    pool.shutdown()
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
