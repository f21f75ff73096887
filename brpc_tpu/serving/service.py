"""Serving registration glue — exposes the batcher and the decode engine
on an ordinary rpc/server.py.

Two methods ride the normal dispatch path (auth, interceptor, limiters,
MethodStatus accounting all apply):

  * ``Serving.Score`` — unary, JSON ``{"x": [floats...]}``; the handler
    defers the RPC into the DynamicBatcher and the batch drainer
    completes it (``{"y": ...}``), ELIMIT-shedding deadline-doomed
    requests up front.
  * ``Serving.Generate`` — streaming, JSON ``{"prompt": [ints...],
    "max_new_tokens": N}`` with a client stream attached
    (``stream_create``); each generated token arrives as one stream
    message ``{"token": t}``, terminated by ``{"done": true}`` and
    stream close (the decode engine's emit drainer writes a step's
    messages of every such stream as one run of frames a connection).

HTTP clients get the same decode stream without a TRPC stack:
``/serving/generate?prompt=1,2,3&max_new_tokens=8`` answers chunked
(ProgressiveAttachment), one JSON line per token.
"""
from __future__ import annotations

import json
from typing import Optional

import numpy as np

from brpc_tpu import errors
from brpc_tpu.rpc.service import Service, method
from brpc_tpu.serving.engine import MessageSink


class ServingService(Service):
    NAME = "Serving"

    def __init__(self, batcher=None, engine=None, prefix_fetcher=None,
                 deployments=None):
        self._batcher = batcher
        self._engine = engine
        # pull-based prefix fetch (ISSUE 16): ``fetch(prompt, holders)
        # -> pages`` — usually brpc_tpu.migrate.make_prefix_fetcher.
        # Set after server start too (the fetcher needs its own addr).
        self.prefix_fetcher = prefix_fetcher
        self.prefix_fetches = 0
        self.prefix_fetched_pages = 0
        # multi-model plane (ISSUE 18): a ReplicaDeployments table maps
        # the forwarded "model" field to per-deployment bindings.  None
        # keeps the legacy single-anonymous-model behavior exactly.
        self.deployments = deployments
        self.n_model_misroutes = 0

    def _resolve(self, cntl, req):
        """``(model_key, bindings)`` for this request.  Without a
        deployment table the constructor bindings apply (model field
        ignored — a pre-plane replica).  A forwarded model this replica
        does not serve fails EINTERNAL — a FAILOVER code, so the
        router's session driver re-routes instead of killing the
        session — and bumps ``n_model_misroutes`` (must stay 0 in a
        healthy fleet: the router constrains picks to the catalog)."""
        model = (req or {}).get("model") or None
        if self.deployments is None or len(self.deployments) == 0:
            return None, {"engine": self._engine,
                          "batcher": self._batcher,
                          "prefix_fetcher": self.prefix_fetcher}
        try:
            key, row = self.deployments.resolve(model)
        except KeyError:
            self.n_model_misroutes += 1
            cntl.set_failed(
                errors.EINTERNAL,
                f"model {model!r} not served by this replica "
                f"(serves {self.deployments.keys()})")
            return None, None
        return key, {"engine": row.get("engine") or self._engine,
                     "batcher": row.get("batcher") or self._batcher,
                     "prefix_fetcher": (row.get("prefix_fetcher")
                                        or self.prefix_fetcher)}

    @method(request="json", response="json")
    def Score(self, cntl, req):
        _, b = self._resolve(cntl, req)
        if b is None:
            return None
        batcher = b["batcher"]
        if batcher is None:
            cntl.set_failed(errors.ENOMETHOD, "no batcher registered")
            return None
        x = (req or {}).get("x")
        if x is None:
            cntl.set_failed(errors.EREQUEST, 'missing "x"')
            return None
        batcher.submit(
            cntl, np.asarray(x, dtype=np.float32),
            transform=lambda row: {"y": np.asarray(row).tolist()})
        return None   # deferred: the batch drainer completes the RPC

    @method(request="tensorframe", response="tensorframe")
    def ScoreT(self, cntl, req):
        """Score on the BINARY tensor wire (ISSUE 17 adopter): the row
        payload rides as a float32 tensor field both ways — no float
        list round-trip.  Old peers never see this; new clients
        (:class:`ScoreClient`) downgrade sticky on ENOMETHOD."""
        _, b = self._resolve(cntl, req)
        if b is None:
            return None
        batcher = b["batcher"]
        if batcher is None:
            cntl.set_failed(errors.ENOMETHOD, "no batcher registered")
            return None
        x = (req or {}).get("x")
        if not isinstance(x, np.ndarray) or x.ndim != 1:
            cntl.set_failed(errors.EREQUEST,
                            'need rank-1 tensor field "x"')
            return None
        batcher.submit(
            cntl, np.asarray(x, dtype=np.float32),
            transform=lambda row: {"y": np.asarray(row, np.float32)})
        return None   # deferred: the batch drainer completes the RPC

    @method(request="json", response="json")
    def Generate(self, cntl, req):
        model_key, b = self._resolve(cntl, req)
        if b is None:
            return None
        engine = b["engine"]
        if engine is None:
            cntl.set_failed(errors.ENOMETHOD, "no decode engine registered")
            return None
        req = req or {}
        prompt = req.get("prompt") or [0]
        max_new = int(req.get("max_new_tokens", 16))
        stream = cntl.accept_stream()

        want_lp = bool(req.get("logprobs"))

        sink = _GenerateSink(stream, want_lp, self.deployments, model_key)

        # advisory prefix probe BEFORE submit: how many prompt tokens
        # the local KV cache can serve without re-decoding.  The
        # cluster router's resume path reads this to account the
        # re-decoded-token cost of a failover (ISSUE 8) — a resume that
        # lands on a replica holding the committed prefix reports
        # prefix_hit > 0 and re-prefills only the tail.
        hit = 0
        store = getattr(engine, "store", None)
        if store is not None and len(prompt) > 1:
            try:
                hit = int(store.probe(prompt))
            except Exception:
                hit = 0
        # pull-based prefix fetch (ISSUE 16): when the router names
        # replicas that hold this prefix (prefix_holders) and the local
        # cache misses the full-page prefix, FETCH it from an owner via
        # the migrator before submitting — a cold replica warms itself
        # instead of re-prefilling.  Any fetch failure falls back to
        # recompute; the generation never depends on it.
        holders = req.get("prefix_holders") or []
        fetcher = b["prefix_fetcher"]
        if (fetcher is not None and holders
                and store is not None and len(prompt) > 1):
            pt = getattr(store, "page_tokens", 16)
            full = len(prompt) // pt * pt
            if full and hit < full:
                try:
                    fetched = int(fetcher(
                        [int(t) for t in prompt],
                        [str(h) for h in holders]))
                except Exception:
                    fetched = 0
                if fetched:
                    self.prefix_fetches += 1
                    self.prefix_fetched_pages += fetched
                    try:
                        hit = max(hit, int(store.probe(prompt)))
                    except Exception:
                        pass
        kw = {}
        if "speculative" in req:
            # per-request opt-out of the engine's draft proposals
            # (ISSUE 11); only forwarded when the client says so, so
            # engine-shaped submitters without the keyword still work
            kw["speculative"] = bool(req["speculative"])
        if want_lp:
            kw["logprobs"] = True
        rid = engine.submit(prompt, max_new, sink, sink.on_done, **kw)
        resp = {"accepted": True, "req_id": rid, "prefix_hit": hit}
        if model_key is not None:
            resp["model"] = model_key
        return resp


class _GenerateSink(MessageSink):
    """``Serving.Generate``'s sink: one JSON message a token on the
    call's stream, ``{"done": true}`` and the stream's close after the
    last.  A ``DecodeEngine`` writes it from its emit drainer, so a
    consumer that stops draining its credit window stalls only itself:
    its tokens wait in its own bounded emit buffer while the shared
    step loop keeps decoding every other slot, and once that overflows
    the engine cuts the request with EOVERCROWDED.  Behind any other
    engine-shaped submitter (a supervisor) it is a callable whose
    bounded write keeps an emitter thread from wedging forever on a
    dead-but-open peer."""

    __slots__ = ("_want_lp", "_deployments", "_model_key")

    def __init__(self, stream, want_lp: bool, deployments=None,
                 model_key=None):
        super().__init__(stream)
        self._want_lp = want_lp
        self._deployments = deployments
        self._model_key = model_key

    def token_message(self, tok: int, logprob) -> bytes:
        msg = {"token": tok}
        if self._want_lp:
            # the served token's log-probability (float32 log-softmax
            # at the runner's stated precision): what chat front ends
            # ask for, and what a check against a reference can hold on
            # every seed where a bare greedy token cannot
            msg["logprob"] = logprob
        return json.dumps(msg).encode()

    def done_message(self, err) -> bytes:
        msg = {"done": True}
        if err is not None:
            msg["error"] = err.code
            msg["error_text"] = err.text
        return json.dumps(msg).encode()

    def on_done(self, err) -> None:
        if err is None and self._model_key is not None \
                and self._deployments is not None:
            # warm-up proof: a completed generation flips this
            # deployment loading -> warm on the published plane
            self._deployments.note_generation(self._model_key)
        super().on_done(err)


class ScoreClient:
    """Client half of the Score adopter (ISSUE 17): prefers the binary
    ``ScoreT`` wire and downgrades STICKY to json ``Score`` when the
    peer answers ENOMETHOD (an old server) — the per-peer negotiation
    contract the PS client runs per shard.  Both paths return the same
    float32 rows; the regression test pins them byte-identical."""

    def __init__(self, channel):
        self._ch = channel
        self._mode: Optional[str] = None     # None | "frame" | "json"
        self.n_negotiation_fallbacks = 0

    @property
    def wire_mode(self) -> Optional[str]:
        return self._mode

    def score(self, x, **kw) -> np.ndarray:
        x = np.ascontiguousarray(x, dtype=np.float32)
        if self._mode != "json":
            try:
                resp = self._ch.call_sync(
                    "Serving", "ScoreT", {"x": x},
                    serializer="tensorframe", **kw)
                self._mode = "frame"
                return np.asarray(resp["y"], np.float32)
            except errors.RpcError as e:
                if e.code != errors.ENOMETHOD:
                    raise
                self._mode = "json"
                self.n_negotiation_fallbacks += 1
        resp = self._ch.call_sync("Serving", "Score",
                                  {"x": x.tolist()},
                                  serializer="json", **kw)
        return np.asarray(resp["y"], np.float32)


def http_generate_handler(engine):
    """Build an HTTP handler streaming decode tokens as chunked JSON
    lines through a ProgressiveAttachment — the no-TRPC client path."""
    from brpc_tpu.rpc.progressive import ProgressiveResponse

    def handler(req):
        try:
            prompt = [int(t) for t in
                      (req.query.get("prompt") or "0").split(",") if t]
            max_new = int(req.query.get("max_new_tokens", "16"))
        except ValueError as e:
            from brpc_tpu.builtin.router import http_response
            return http_response(400, f"bad query: {e}\n")

        def writer(pa):
            def emit(tok: int) -> None:
                # ProgressiveAttachment.write returns -1 (never raises)
                # once the connection died; raising here makes the
                # engine retire the slot instead of decoding to nobody
                if pa.write(json.dumps({"token": tok}) + "\n") != 0:
                    raise errors.RpcError(errors.EFAILEDSOCKET,
                                          "http client gone")

            def on_done(err) -> None:
                msg = {"done": True}
                if err is not None:
                    msg["error"] = err.code
                pa.write(json.dumps(msg) + "\n")
                pa.close()

            engine.submit(prompt, max_new, emit, on_done)

        return ProgressiveResponse(writer,
                                   content_type="application/json-seq")

    return handler


def register_serving(server, batcher=None, engine=None,
                     prefix_fetcher=None, deployments=None,
                     http_generate_path: Optional[str]
                     = "/serving/generate") -> ServingService:
    """Register the serving surface on a Server: the Serving service
    (Score/Generate) plus the chunked HTTP generate route.  Call before
    ``server.start()``."""
    svc = ServingService(batcher, engine, prefix_fetcher,
                         deployments=deployments)
    server.add_service(svc)
    if engine is not None and http_generate_path:
        server.add_http_handler(http_generate_path,
                                http_generate_handler(engine))
    return svc
