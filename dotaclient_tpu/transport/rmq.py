"""RabbitMQ broker — drop-in for deployments that run the reference's
transport (SURVEY.md §1 L3: durable `experience` queue, `model` fanout
exchange). Requires `pika`, which is intentionally a soft dependency: the
image this framework develops in does not ship it, and mem:///tcp://
cover every test and single-cluster path. Import errors surface with a
clear message instead of at module import time.

Failure model (r5 VERDICT item 6 — this broker had never executed
against a mid-stream failure): every public operation runs under a
bounded reconnect-retry loop (transport.base.RetryPolicy — the same
jittered window/backoff shape the tcp client uses). On a connection
reset, channel close, or publish return the client tears the connection
down, rebuilds the full topology (queue, exchange, qos, model binding,
consumer registration), and retries the operation until the retry
window expires:

- a failed PUBLISH is resent after reconnect. The client cannot know
  whether the broker enqueued the frame before the stream died, so
  delivery is at-least-once — a possible duplicate rollout is harmless
  to PPO (same stance as the tcp client's whole-message resend);
- a failed CONSUME drops the client-side unacked buffer (its delivery
  tags died with the channel) and relies on AMQP redelivery: the broker
  requeues unacked deliveries on channel death, so frames are not lost
  (tests/test_rmq.py proves exactly-once observable delivery across an
  injected mid-consume channel close);
- a publish RETURN (unroutable — topology missing, e.g. a broker that
  restarted empty) is handled by the same reconnect path, whose
  re-declaration recreates the queue before the resend.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Deque, List, Optional

from dotaclient_tpu.transport.base import Broker, RetryPolicy

_log = logging.getLogger(__name__)

EXPERIENCE_QUEUE = "experience"
MODEL_EXCHANGE = "model"

# pika exception names treated as retryable-with-reconnect; resolved
# lazily against whatever pika (real or tests/fake_pika) is installed.
_RETRYABLE_NAMES = (
    "AMQPConnectionError",
    "ConnectionClosed",
    "StreamLostError",
    "ConnectionWrongStateError",
    "AMQPChannelError",
    "ChannelClosed",
    "ChannelClosedByBroker",
    "ChannelWrongStateError",
    "UnroutableError",
)


class RmqBroker(Broker):
    def __init__(self, url: str, prefetch: int = 512, retry: Optional[RetryPolicy] = None):
        try:
            import pika  # noqa: F401
        except ImportError as e:  # pragma: no cover - exercised only with pika
            raise ImportError(
                "amqp:// broker URLs require the 'pika' package; use mem:// "
                "or tcp:// (dotaclient_tpu.transport.tcp_server) instead"
            ) from e
        import pika

        self._pika = pika
        self._params = pika.URLParameters(url)
        self._prefetch = prefetch
        self._retry = retry if retry is not None else RetryPolicy()
        self._retryable = tuple(
            getattr(pika.exceptions, n) for n in _RETRYABLE_NAMES if hasattr(pika.exceptions, n)
        ) + (OSError,)
        self._lock = threading.Lock()
        self.reconnects = -1  # the boot connect brings it to 0
        self._connect()  # fail fast at boot — a wrong URL should not retry

    def _connect(self) -> None:
        """(Re)build the connection and the FULL topology. Called at boot
        and after any mid-stream failure; must leave the client exactly
        as a fresh one — in particular the unacked buffer is dropped
        (its delivery tags died with the old channel; the broker
        redelivers) and the consumer registration reset."""
        pika = self._pika
        self._conn = pika.BlockingConnection(self._params)
        self._ch = self._conn.channel()
        self._ch.queue_declare(queue=EXPERIENCE_QUEUE, durable=True)
        self._ch.exchange_declare(exchange=MODEL_EXCHANGE, exchange_type="fanout")
        self._ch.basic_qos(prefetch_count=self._prefetch)
        # Per-subscriber exclusive queue bound to the model fanout. A
        # reconnect gets a FRESH queue: broadcasts published while we
        # were down are gone, which is correct for latest-wins weights
        # (the next publish reaches us).
        res = self._ch.queue_declare(queue="", exclusive=True)
        self._model_queue = res.method.queue
        self._ch.queue_bind(exchange=MODEL_EXCHANGE, queue=self._model_queue)
        # Long-lived experience consumer, registered lazily on the FIRST
        # consume_experience call: only the learner consumes, so actor-side
        # brokers never register one (a registered consumer would steal
        # frames). Messages land in _exp_buf from process_data_events.
        #
        # Acking is explicit (auto_ack=False): a delivery is acked only
        # when consume_experience hands it to the caller. That makes
        # basic_qos(prefetch) actually bind client-side buffering —
        # at most `prefetch` frames sit unacked in _exp_buf, the rest of
        # a backlog stays on the broker (visible in experience_depth,
        # redelivered if this process dies). auto_ack would pull the
        # whole backlog into process memory and lose it on crash.
        self._exp_buf: Deque[tuple] = deque()  # (delivery_tag, body)
        self._consuming = False
        self.reconnects += 1

    def _teardown(self) -> None:
        try:
            self._conn.close()
        except Exception:
            pass  # a half-dead connection may throw from close

    def _run_with_reconnect(self, op):
        """Run `op()` (caller holds self._lock), reconnecting with the
        jittered capped backoff on any retryable AMQP failure, for up to
        the retry window. Mirrors the tcp client's _Conn.request loop."""
        deadline = time.monotonic() + self._retry.window_s
        backoff = self._retry.backoff_base_s
        while True:
            try:
                return op()
            except self._retryable as e:
                self._teardown()
                if time.monotonic() >= deadline:
                    raise
                _log.warning("amqp op failed (%s: %s); reconnecting", type(e).__name__, e)
                time.sleep(self._retry.sleep_for(backoff))
                backoff = self._retry.next_backoff(backoff)
                try:
                    self._connect()
                except self._retryable:
                    # broker still down: burn the next backoff slice and
                    # let the loop re-check the deadline
                    continue

    def _on_experience(self, _ch, method, _props, body) -> None:
        self._exp_buf.append((method.delivery_tag, body))

    def publish_experience(self, data: bytes) -> None:
        def op():
            self._ch.basic_publish(
                exchange="",
                routing_key=EXPERIENCE_QUEUE,
                body=data,
                properties=self._pika.BasicProperties(delivery_mode=2),
            )

        with self._lock:
            self._run_with_reconnect(op)

    def consume_experience(self, max_items: int, timeout: Optional[float] = None) -> List[bytes]:
        # Contract (transport.base): block up to `timeout` (None = forever)
        # for the FIRST frame only, then drain without waiting. The
        # deadline is computed OUTSIDE the retried op so a mid-wait
        # reconnect resumes the same wait instead of restarting it.
        deadline = None if timeout is None else time.monotonic() + timeout

        def op():
            if not self._consuming:
                self._ch.basic_consume(
                    EXPERIENCE_QUEUE, on_message_callback=self._on_experience, auto_ack=False
                )
                self._consuming = True
            while not self._exp_buf:
                if deadline is None:
                    slice_s = 0.2
                else:
                    slice_s = deadline - time.monotonic()
                    if slice_s <= 0:
                        break
                # pump I/O: deliveries invoke _on_experience
                self._conn.process_data_events(time_limit=min(slice_s, 0.2))
            out: List[bytes] = []
            # drain whatever has been prefetched, no further waiting
            self._conn.process_data_events(time_limit=0)
            last_tag = None
            while self._exp_buf and len(out) < max_items:
                last_tag, body = self._exp_buf.popleft()
                out.append(body)
            if last_tag is not None:
                # tags are per-channel monotonic and we pop in order, so
                # one cumulative ack covers everything handed out
                self._ch.basic_ack(delivery_tag=last_tag, multiple=True)
            return out

        with self._lock:
            return self._run_with_reconnect(op)

    def publish_weights(self, data: bytes) -> None:
        # pika frames a `bytes` body and nothing else; the learner's
        # frame is a read-only buffer (serialize_weights)
        body = data if isinstance(data, bytes) else bytes(data)

        def op():
            self._ch.basic_publish(exchange=MODEL_EXCHANGE, routing_key="", body=body)

        with self._lock:
            self._run_with_reconnect(op)

    def poll_weights(self) -> Optional[bytes]:
        def op():
            latest = None
            while True:
                method, _props, body = self._ch.basic_get(self._model_queue, auto_ack=True)
                if body is None:
                    break
                latest = body  # drain to the newest (latest-wins fanout)
            return latest

        with self._lock:
            return self._run_with_reconnect(op)

    def experience_depth(self) -> int:
        def op():
            # passive declare's message_count is READY messages only
            # (excludes unacked deliveries); add what sits unacked in our
            # buffer so the gauge reports the true backlog.
            res = self._ch.queue_declare(queue=EXPERIENCE_QUEUE, durable=True, passive=True)
            return res.method.message_count + len(self._exp_buf)

        with self._lock:
            return self._run_with_reconnect(op)

    def close(self) -> None:
        # _teardown, not a bare close: after an exhausted retry window
        # the connection is already closed, and real pika raises
        # ConnectionWrongStateError on closing a closed connection — a
        # clean shutdown must not crash on it.
        with self._lock:
            self._teardown()
