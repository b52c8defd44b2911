#!/usr/bin/env python3
"""The load generator: a child process with its own event loop, which
imports neither JAX nor the program, so it shares no interpreter lock
with the server and can never touch the chip.

``run.py`` starts ``python3 benchmark/loadgen.py`` and writes one JSON
line to its stdin (the spec: port, traffic file, seed, seconds, the
monotonic time ``t0`` at which the window opens, ...). The child builds
the schedule from the seed (``traffic.schedule``), offers the ramp,
measures the requests **due** inside the window, drains, and prints one
JSON line. ``time.monotonic`` is one clock for every process of a Linux
machine, so parent and child agree on ``t0`` without a handshake.

All times are taken here, on the client's side of a real socket:

- a request is timed from when it was *due*, not from when it was sent;
- how late it was sent is reported (``late_ms``): a starved generator
  must not read as a fast server;
- a request that errors, is refused, returns the wrong number of frames
  or is still open when the drain ends is failed, and the parent counts
  it as the worst latency.
"""

from __future__ import annotations

import asyncio
import json
import os
import resource
import sys
import time
from typing import Any, Dict, List

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import traffic as traffic_mod  # noqa: E402  (sibling file, no package)

HOST = "127.0.0.1"


class Generator:
    def __init__(self, spec: Dict[str, Any]):
        self.spec = spec
        self.kind = spec["kind"]
        self.port = int(spec["port"])
        self.seed = int(spec["seed"])
        self.t0 = float(spec["t0"])
        self.t_end = self.t0 + float(spec["seconds"])
        self.traffic = spec["traffic"]
        self.read_timeout = float(spec.get("read_timeout_s", 60.0))
        self.records: List[Dict[str, Any]] = []
        self.frames_in_window = 0
        self.errors: List[str] = []
        self.idle: List[Any] = []           # keep-alive connections
        if self.kind == "classify":
            shape = tuple(spec["input_shape"])
            images = traffic_mod.images(self.seed, int(spec["inputs"]),
                                        shape)
            head = (f"POST {spec['path']} HTTP/1.1\r\nHost: bench\r\n"
                    f"Content-Type: application/x-tensor\r\n"
                    f"X-Tensor-Dtype: uint8\r\n"
                    f"X-Tensor-Shape: {','.join(map(str, shape))}\r\n"
                    f"Content-Length: {images[0].nbytes}\r\n\r\n").encode()
            self.payloads = [head + image.tobytes() for image in images]

    # -- one request ---------------------------------------------------------
    def _note_error(self, message: str) -> None:
        if len(self.errors) < 8:
            self.errors.append(message[:300])

    async def _generate(self, request: Dict[str, Any],
                        due: float) -> Dict[str, Any]:
        """One SSE stream on a fresh connection. Frames are stamped as
        they are read; the stream must end with ``[DONE]`` after exactly
        its budget of in-vocabulary tokens."""
        budget = int(request["max_new_tokens"])
        body = json.dumps({
            "prompt_ids": traffic_mod.prompt_ids(
                self.seed, request["id"], int(request["prompt_len"]),
                int(self.spec["vocab"])),
            "max_new_tokens": budget}).encode()
        record = {"id": request["id"], "due": due, "ok": False,
                  "frames": 0, "budget": budget}
        writer = None
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(HOST, self.port), self.read_timeout)
            writer.write((f"POST {self.spec['path']} HTTP/1.1\r\n"
                          f"Host: bench\r\nConnection: close\r\n"
                          f"Content-Type: application/json\r\n"
                          f"Content-Length: {len(body)}\r\n\r\n").encode()
                         + body)
            await writer.drain()
            record["sent"] = time.monotonic()
            status_line = await asyncio.wait_for(reader.readline(),
                                                 self.read_timeout)
            status = int(status_line.split()[1])
            done = False
            vocab = int(self.spec["vocab"])
            while True:
                line = await asyncio.wait_for(reader.readline(),
                                              self.read_timeout)
                if not line:
                    break
                if not line.startswith(b"data: "):
                    continue
                now = time.monotonic()
                payload = line[6:].strip()
                if payload == b"[DONE]":
                    done = True
                    continue
                token = json.loads(payload)["token"]
                if not 0 <= int(token) < vocab:
                    raise ValueError(f"token {token} out of vocabulary")
                record["frames"] += 1
                record.setdefault("first", now)
                record["last"] = now
                if self.t0 <= now < self.t_end:
                    self.frames_in_window += 1
            record["end"] = time.monotonic()
            if status != 200:
                raise ValueError(f"status {status}")
            if not done:
                raise ValueError("stream ended without [DONE]")
            if record["frames"] != budget:
                raise ValueError(f"{record['frames']} frames, budget "
                                 f"{budget}")
            record["ok"] = True
        except Exception as exc:  # noqa: BLE001 — any failure is a failed request
            record["error"] = f"{type(exc).__name__}: {exc}"
            record.setdefault("end", time.monotonic())
            self._note_error(record["error"])
        finally:
            if writer is not None:
                writer.close()
        return record

    async def _classify(self, request: Dict[str, Any],
                        due: float) -> Dict[str, Any]:
        """One image on a keep-alive connection; the response is complete
        when its whole body has been read."""
        record = {"id": request["id"], "due": due, "ok": False}
        conn = None
        try:
            conn = self.idle.pop() if self.idle else \
                await asyncio.wait_for(
                    asyncio.open_connection(HOST, self.port),
                    self.read_timeout)
            reader, writer = conn
            image = request["id"] % len(self.payloads)
            writer.write(self.payloads[image])
            await writer.drain()
            record["sent"] = time.monotonic()
            head = await asyncio.wait_for(reader.readuntil(b"\r\n\r\n"),
                                          self.read_timeout)
            status = int(head.split(b" ", 2)[1])
            length = 0
            for line in head.split(b"\r\n"):
                if line.lower().startswith(b"content-length:"):
                    length = int(line.split(b":", 1)[1])
            body = await asyncio.wait_for(reader.readexactly(length),
                                          self.read_timeout)
            record["end"] = time.monotonic()
            data = json.loads(body)["data"]
            if status not in (200, 201):
                raise ValueError(f"status {status}")
            if not 0 <= int(data["label"]) < int(self.spec["classes"]):
                raise ValueError(f"label {data['label']} out of range")
            if data["score"] != data["score"]:
                raise ValueError("score is NaN")
            record["ok"] = True
            record["image"] = image
            record["label"] = int(data["label"])
            self.idle.append(conn)
            conn = None
        except Exception as exc:  # noqa: BLE001
            record["error"] = f"{type(exc).__name__}: {exc}"
            record.setdefault("end", time.monotonic())
            self._note_error(record["error"])
        finally:
            if conn is not None:
                conn[1].close()
        return record

    async def _one(self, request: Dict[str, Any], due: float,
                   measured: bool) -> None:
        record = {"id": request["id"], "due": due, "ok": False}
        try:
            record = await (self._generate(request, due)
                            if self.kind == "generate"
                            else self._classify(request, due))
        except asyncio.CancelledError:
            record["error"] = "still open when the drain ended"
            raise
        finally:
            record["measured"] = measured
            self.records.append(record)

    # -- the two loops -------------------------------------------------------
    async def open_loop(self, requests: List[Dict[str, Any]]) -> None:
        index, of = int(self.spec["index"]), int(self.spec["of"])
        mine = [r for i, r in enumerate(requests) if i % of == index]
        tasks = []
        for request in mine:
            due = self.t0 + request["due"]
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            tasks.append(asyncio.ensure_future(
                self._one(request, due, request["measured"])))
        await self._drain(tasks)

    async def closed_loop(self, sequences: List[List[Dict[str, Any]]]
                          ) -> None:
        index, of = int(self.spec["index"]), int(self.spec["of"])
        ramp = float(self.traffic["ramp_s"])

        async def client(c: int, sequence) -> None:
            # clients join over the first half of the ramp, so that the
            # first admission is not one burst of every client at once
            start = self.t0 - ramp + 0.5 * ramp * c / max(1, len(sequences))
            await asyncio.sleep(max(0.0, start - time.monotonic()))
            due = time.monotonic()
            for request in sequence:
                if due >= self.t_end:
                    return
                await self._one(request, due, True)
                due = time.monotonic()      # the next is due at once

        tasks = [asyncio.ensure_future(client(c, sequence))
                 for c, sequence in enumerate(sequences) if c % of == index]
        # after the window no client starts a request (``due >= t_end``);
        # the streams still open run to their end inside the drain, so
        # that no stream is cut off under the server. They are not
        # attempts of this window.
        await self._drain(tasks)
        self.records = [r for r in self.records
                        if "end" in r and self.t0 <= r["end"] < self.t_end]

    async def _drain(self, tasks) -> None:
        limit = self.t_end + float(self.traffic["drain_s"])
        if tasks:
            _done, pending = await asyncio.wait(
                tasks, timeout=max(0.0, limit - time.monotonic()))
            for task in pending:
                task.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

    async def run(self) -> Dict[str, Any]:
        plan = traffic_mod.schedule(self.traffic, self.seed,
                                    float(self.spec["seconds"]))
        if plan["loop"] == "open":
            await self.open_loop(plan["requests"])
        else:
            await self.closed_loop(plan["clients"])
        for _reader, writer in self.idle:
            writer.close()
        measured = [r for r in self.records if r["measured"]]
        for record in measured:       # times as offsets from the window
            for key in ("due", "sent", "first", "last", "end"):
                if key in record:
                    record[key] = round(record[key] - self.t0, 6)
        return {"index": self.spec["index"], "records": measured,
                "ramp_requests": len(self.records) - len(measured),
                "frames_in_window": self.frames_in_window,
                "errors": self.errors}


def main() -> None:
    spec = json.loads(sys.stdin.readline())
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    result = asyncio.run(Generator(spec).run())
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()


if __name__ == "__main__":
    main()
