"""Out-of-process replica worker: one ``ServingEngine`` behind the framed RPC.

Spawned by ``serving/transport.py``'s ``EngineClient`` as::

    python -m perceiver_io_tpu.serving.worker --fd <socket fd>

and driven entirely over that socket — one CRC-framed pickle request in, one
framed reply out (frame format and reliability contract in transport.py's
module docstring). The first op must be ``init``: it ships the pickled
model, numpy params, the fleet's engine knobs, the replica's journal
directory, and the client's ``jax_enable_x64`` flag (applied BEFORE the
engine builds, so the f64 token-identity pins hold across the process
boundary). Telemetry is forced off in the worker — spans cannot usefully
cross process lines; the journal and metrics JSONL write from HERE, the
process that owns the engine, so crash durability semantics are unchanged.

Protocol guarantees implemented on this side:

  * **NACK, don't execute** — a frame failing CRC gets a ``seq=None`` error
    reply and nothing runs; the client retries the op from scratch.
  * **At-most-once** — replies are cached by ``seq``; a retried ``seq``
    (the client timed out reading the reply) is answered from the cache
    byte-identically, WITHOUT re-executing the op.
  * **State bundle** — every reply carries the engine state the router
    reads between calls (load, has_work, compilations, latency estimates,
    live handle states, newly finished handles, the journal's live-rid
    set), so the client's mirrors stay current at zero extra round trips.
"""

from __future__ import annotations

import argparse
import pickle
import socket
import traceback
from typing import Dict, Optional

from perceiver_io_tpu.serving.transport import FrameError, encode_frame, recv_frame


def _req_state(h) -> Dict:
    """The mirror-refresh slice of one handle's state (client applies it via
    ``EngineClient._update_mirror``)."""
    return {
        "status": h.status.value,
        "finish_reason": h.finish_reason,
        "output_ids": list(h.output_ids),
        "admitted_at": h.admitted_at,
        "finished_at": h.finished_at,
        "preemptions": h.preemptions,
        "slot": h.slot,
    }


def _full_state(h) -> Dict:
    """Everything needed to CONSTRUCT a mirror client-side (submit /
    recover_attach replies)."""
    import numpy as np

    st = _req_state(h)
    st.update({
        "rid": h.request_id,
        "prompt": np.asarray(h.prompt_ids, np.int32),
        "config": h.config,
        "rng": np.asarray(h.rng, np.uint32),
        "priority": h.priority,
        "deadline_s": h.deadline_s,
        "session_id": h.session_id,
        "version": h.version,
        "is_resume": h.is_resume,
    })
    return st


class _Worker:
    def __init__(self):
        self.engine = None

    # ------------------------------------------------------------------ ops
    def op_init(self, p):
        if self.engine is not None:
            raise RuntimeError("worker already initialized")
        import jax

        jax.config.update("jax_enable_x64", bool(p["x64"]))
        from perceiver_io_tpu.compile_cache import enable_compile_cache
        from perceiver_io_tpu.serving.engine import ServingEngine

        enable_compile_cache()  # a restarted replica finds its programs again
        self.engine = ServingEngine(
            p["model"], p["params"],
            metrics_jsonl=p["metrics_jsonl"],
            journal=p["journal"],
            telemetry=False,
            obs_ns=p["obs_ns"],
            **p["engine_kwargs"],
        )
        return {"journaled": self.engine.journal is not None}

    def op_submit(self, p):
        handle = self.engine.submit(
            p["prompt"], config=p["config"],
            rng=p["rng"],
            deadline_s=p["deadline_s"],
            replay_ids=p["replay_ids"],
            priority=p["priority"],
            resume=p["resume"],
            session_id=p["session_id"],
            version=p["version"],
            **(p["kwargs"] or {}),
        )
        return {"state": _full_state(handle)}

    def op_step_dispatch(self, p):
        return bool(self.engine.step_dispatch())

    def op_step_harvest(self, p):
        self.engine.step_harvest()

    def op_discard_pending_harvest(self, p):
        self.engine.discard_pending_harvest()

    def op_begin_drain(self, p):
        self.engine._begin_drain()

    def op_evict(self, p):
        from perceiver_io_tpu.serving.engine import RequestStatus

        handle = self.engine.evict_request(
            p["rid"], p["reason"], status=RequestStatus(p["status"]),
            queued_only=p["queued_only"],
            journal_terminal=p["journal_terminal"],
        )
        return handle is not None

    def op_mark_resume(self, p):
        self.engine.mark_resume(p["rid"])

    def op_set_params(self, p):
        self.engine.set_params(p["params"])

    def op_journal_tick(self, p):
        journal = self.engine.journal
        if journal is None:
            raise RuntimeError("engine has no journal")
        journal.append_tick(p["admitted"], p["tokens"],
                            [tuple(t) for t in p["terminals"]])

    def op_snapshot(self, p):
        return self.engine.metrics.snapshot()

    def op_recover_attach(self, p):
        info = self.engine._recover_attach(
            p["path"], fsync=p["fsync"],
            segment_max_records=p["segment_max_records"],
            skip_session_ids=frozenset(p["skip_session_ids"]),
        )
        info["handle_states"] = [_full_state(h) for h in info.pop("handles")]
        return info

    def op_close(self, p):
        if self.engine is not None:
            self.engine.close()

    # ---------------------------------------------------------------- bundle
    def bundle(self) -> Optional[Dict]:
        engine = self.engine
        if engine is None:
            return None
        finished = [(h.request_id, _req_state(h)) for h in engine.finished]
        engine.finished = []  # shipped: the CLIENT list owns them now
        journal = engine.journal
        return {
            "load": engine.load,
            "has_work": engine.scheduler.has_work,
            "total_compilations": engine.total_compilations,
            "latency_estimates": engine.metrics.latency_estimates(),
            "requests": {rid: _req_state(h)
                         for rid, h in engine._requests.items()},
            "finished": finished,
            "journal_live": (sorted(journal._live) if journal is not None
                             else None),
            "journal_failed": journal.failed if journal is not None else False,
        }

    # ------------------------------------------------------------------ loop
    def serve(self, sock: socket.socket) -> None:
        replies: Dict[int, bytes] = {}
        order = []
        while True:
            try:
                payload = recv_frame(sock)
            except FrameError:
                # torn frame: reject WITHOUT executing — the client retries
                nack = pickle.dumps({
                    "seq": None, "ok": False,
                    "error": ("FrameError", "frame crc mismatch", ""),
                    "state": None,
                }, protocol=pickle.HIGHEST_PROTOCOL)
                sock.sendall(encode_frame(nack))
                continue
            except (EOFError, OSError):
                return  # client gone: nothing to serve
            msg = pickle.loads(payload)
            seq = msg["seq"]
            if seq in replies:
                # duplicate of an executed op (the client timed out reading
                # the reply): answer from the cache, at-most-once
                sock.sendall(replies[seq])
                continue
            op = msg["op"]
            handler = getattr(self, f"op_{op}", None)
            try:
                if handler is None:
                    raise ValueError(f"unknown op {op!r}")
                value = handler(msg["payload"])
                reply = {"seq": seq, "ok": True, "value": value}
            except BaseException as e:  # noqa: BLE001 — ship it to the client
                reply = {"seq": seq, "ok": False,
                         "error": (type(e).__name__, str(e),
                                   traceback.format_exc())}
            reply["state"] = self.bundle()
            raw = encode_frame(pickle.dumps(reply,
                                            protocol=pickle.HIGHEST_PROTOCOL))
            replies[seq] = raw
            order.append(seq)
            while len(order) > 8:  # the client never retries further back
                replies.pop(order.pop(0), None)
            try:
                sock.sendall(raw)
            except OSError:
                return
            if op == "close":
                return


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--fd", type=int, required=True,
                        help="inherited socketpair fd connected to the client")
    args = parser.parse_args()
    sock = socket.socket(fileno=args.fd)
    try:
        _Worker().serve(sock)
    finally:
        try:
            sock.close()
        except OSError:
            pass


if __name__ == "__main__":
    main()
