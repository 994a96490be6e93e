"""Serving stack end-to-end: InferenceModel, ClusterServing over loopback,
client queues, error paths, backpressure, and the HTTP frontend.

Reference test strategy (SURVEY.md §4.3): serving pre/post-processing and
engine specs ran on a Flink MiniCluster + local Redis.  The analog here is
the real server on a loopback port with real sockets and threads.
"""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.core import init_orca_context
from analytics_zoo_tpu.serving import (ClusterServing, HTTPFrontend,
                                       InferenceModel, InputQueue,
                                       OutputQueue)
from analytics_zoo_tpu.serving import protocol


def _linear_model():
    init_orca_context("local")

    class M(nn.Module):
        def forward(self, scope, x):
            return scope.child(nn.Dense(3), x, name="fc")

    m = M()
    variables = m.init(__import__("jax").random.PRNGKey(0),
                       np.zeros((1, 4), np.float32))
    return m, variables


@pytest.fixture(scope="module")
def inference_model():
    m, variables = _linear_model()
    return InferenceModel(batch_buckets=(1, 4, 8)).load(m, variables)


# -- InferenceModel alone -----------------------------------------------------

def test_inference_model_bucket_padding(inference_model):
    x = np.random.default_rng(0).normal(size=(3, 4)).astype(np.float32)
    out = inference_model.predict(x)
    assert out.shape == (3, 3)
    # per-row result must not depend on bucket padding
    row0 = inference_model.predict(x[:1])
    np.testing.assert_allclose(out[0], row0[0], rtol=1e-5)


def test_inference_model_chunking(inference_model):
    x = np.random.default_rng(1).normal(size=(19, 4)).astype(np.float32)
    out = inference_model.predict(x)          # 19 > largest bucket (8)
    assert out.shape == (19, 3)
    np.testing.assert_allclose(out[:4], inference_model.predict(x[:4]),
                               rtol=1e-5)


# -- ClusterServing round-trips ----------------------------------------------

def test_serving_round_trip(inference_model):
    with ClusterServing(inference_model, batch_size=4) as srv:
        iq = InputQueue(srv.host, srv.port)
        oq = OutputQueue(input_queue=iq)
        x = np.arange(4, dtype=np.float32)
        uid = iq.enqueue("t", t=x)
        out = oq.query(uid, timeout=20.0)
        assert out is not None and out.shape == (3,)
        expect = inference_model.predict(x[None])[0]
        np.testing.assert_allclose(out, expect, rtol=1e-5)


def test_serving_concurrent_mixed_shapes(inference_model):
    """Many clients, two different feature shapes, all answered correctly."""
    with ClusterServing(inference_model, batch_size=8,
                        batch_timeout_ms=20) as srv:
        results = {}
        errors = []

        def client(i):
            try:
                iq = InputQueue(srv.host, srv.port)
                oq = OutputQueue(input_queue=iq)
                x = np.full((4,), float(i), np.float32)
                uid = iq.enqueue(f"c{i}", t=x)
                out = oq.query(uid, timeout=30.0)
                results[i] = out
            except Exception as e:  # noqa: BLE001
                errors.append((i, e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert len(results) == 12
        for i, out in results.items():
            expect = inference_model.predict(
                np.full((1, 4), float(i), np.float32))[0]
            np.testing.assert_allclose(out, expect, rtol=1e-5)


def test_serving_survives_header_only_frame(inference_model):
    """ADVICE r1 (high): a header-only frame must get an error reply and must
    NOT kill the batcher thread for everyone else."""
    import socket
    with ClusterServing(inference_model, batch_size=2) as srv:
        raw = socket.create_connection((srv.host, srv.port), timeout=10)
        try:
            protocol.send_frame(raw, protocol.encode({"uuid": "bad-1"}))
            reply = protocol.recv_frame(raw)
            header, arr = protocol.decode(reply)
            assert header["uuid"] == "bad-1" and "error" in header
        finally:
            raw.close()
        # the server must still answer a valid request afterwards
        iq = InputQueue(srv.host, srv.port)
        oq = OutputQueue(input_queue=iq)
        uid = iq.enqueue("ok", t=np.ones(4, np.float32))
        assert oq.query(uid, timeout=20.0) is not None


class _SlowModel:
    """Stub standing in for InferenceModel: slow + optionally failing."""

    def __init__(self, delay=0.0, fail=False):
        self.delay = delay
        self.fail = fail

    def predict(self, x):
        if self.delay:
            time.sleep(self.delay)
        if self.fail:
            raise ValueError("boom")
        return np.asarray(x) * 2.0


def test_serving_error_reply_reaches_client():
    with ClusterServing(_SlowModel(fail=True), batch_size=2) as srv:
        iq = InputQueue(srv.host, srv.port)
        oq = OutputQueue(input_queue=iq)
        uid = iq.enqueue("t", t=np.ones(4, np.float32))
        with pytest.raises(RuntimeError, match="boom"):
            oq.query(uid, timeout=20.0)
        # batcher survives a failing model too
        uid2 = iq.enqueue("t2", t=np.ones(4, np.float32))
        with pytest.raises(RuntimeError, match="boom"):
            oq.query(uid2, timeout=20.0)


def test_serving_backpressure_queue_full():
    """With a 1-slot queue, a slow model, and a tiny push timeout, floods get
    explicit 'queue full' error replies instead of silent drops.  Retries
    are disabled so the raw server-side rejection reaches the caller
    (the default client retries these — tests/test_robustness.py)."""
    from analytics_zoo_tpu.serving.client import RetryPolicy
    with ClusterServing(_SlowModel(delay=0.3), batch_size=1,
                        queue_items=1, push_timeout=0.05) as srv:
        iq = InputQueue(srv.host, srv.port,
                        retry=RetryPolicy(max_attempts=1))
        oq = OutputQueue(input_queue=iq)
        uids = [iq.enqueue(f"f{i}", t=np.ones(2, np.float32))
                for i in range(8)]
        outcomes = {"ok": 0, "full": 0}
        for uid in uids:
            try:
                out = oq.query(uid, timeout=30.0)
                if out is not None:
                    outcomes["ok"] += 1
            except RuntimeError as e:
                assert "queue full" in str(e)
                outcomes["full"] += 1
        assert outcomes["ok"] >= 1     # service still makes progress
        assert outcomes["full"] >= 1   # and sheds load explicitly


def test_native_queue_empty_payload():
    """ADVICE r1 (low): a zero-length payload is a valid item, not a
    timeout."""
    from analytics_zoo_tpu.native import NativeQueue
    q = NativeQueue(max_items=4)
    assert q.push(b"", tag=7)
    item = q.pop(timeout=1.0)
    assert item is not None
    payload, tag = item
    assert payload == b"" and tag == 7


def test_native_queue_builds_from_source_on_first_use(tmp_path,
                                                      monkeypatch):
    """No binary is kept in git: with the library absent, first use
    compiles zoo_native.cpp and the queue is the C++ one."""
    from analytics_zoo_tpu import native
    so = tmp_path / "libzoonative-test.so"
    monkeypatch.setattr(native, "_so_path", lambda: str(so))
    monkeypatch.setattr(native, "_lib", None)
    q = native.NativeQueue(max_items=2)
    assert so.exists()
    assert q.is_native
    assert q.push(b"abc", tag=1) and q.pop(timeout=1.0) == (b"abc", 1)


# -- HTTP frontend ------------------------------------------------------------

def test_http_frontend(inference_model):
    with ClusterServing(inference_model, batch_size=4) as srv:
        with HTTPFrontend(srv.host, srv.port) as fe:
            url = f"http://{fe.host}:{fe.port}"
            with urllib.request.urlopen(url + "/health", timeout=10) as r:
                assert json.load(r)["status"] == "ok"
            req = urllib.request.Request(
                url + "/predict",
                data=json.dumps({"instances": [[1, 2, 3, 4]]}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                pred = json.load(r)["predictions"]
            expect = inference_model.predict(
                np.asarray([[1, 2, 3, 4]], np.float32))
            np.testing.assert_allclose(np.asarray(pred), expect, rtol=1e-4)


def test_http_frontend_bad_request(inference_model):
    with ClusterServing(inference_model, batch_size=4) as srv:
        with HTTPFrontend(srv.host, srv.port) as fe:
            url = f"http://{fe.host}:{fe.port}/predict"
            req = urllib.request.Request(
                url, data=b'{"wrong": 1}',
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 400


def test_http_frontend_reconnects_after_backend_restart(inference_model):
    """A backend restart must not permanently kill the HTTP frontend."""
    srv = ClusterServing(inference_model, batch_size=4).start()
    port = srv.port
    fe = HTTPFrontend(srv.host, port).start()
    try:
        x = np.ones((1, 4), np.float32)
        assert fe.predict(x) is not None
        srv.stop()
        deadline = time.time() + 10
        while True:  # wait for the OS to release the port
            try:
                srv = ClusterServing(inference_model, port=port,
                                     batch_size=4).start()
                break
            except OSError:
                if time.time() > deadline:
                    raise
                time.sleep(0.1)
        out = fe.predict(x)  # reconnect happens inside predict
        assert out is not None
        np.testing.assert_allclose(np.squeeze(out),
                                   np.squeeze(inference_model.predict(x)),
                                   rtol=1e-5)
    finally:
        fe.stop()
        srv.stop()


def test_serving_and_frontend_stats(inference_model):
    with ClusterServing(inference_model, batch_size=4) as srv:
        with HTTPFrontend(srv.host, srv.port) as fe:
            url = f"http://{fe.host}:{fe.port}"
            for _ in range(3):
                req = urllib.request.Request(
                    url + "/predict",
                    data=json.dumps({"instances": [[1, 2, 3, 4]]}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30):
                    pass
            with urllib.request.urlopen(url + "/stats", timeout=10) as r:
                fstats = json.load(r)
            assert fstats["requests"] == 3 and fstats["timeouts"] == 0
        s = srv.stats()
        assert s["requests"] == 3 and s["replies"] == 3
        assert s["batches"] >= 1 and s["errors"] == 0
        assert 1.0 <= s["mean_batch_size"] <= 4.0


def test_inference_model_bf16_serving_dtype():
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    m = nn.Sequential([nn.Dense(8, activation="relu"), nn.Dense(3)])
    v = m.init(jax.random.PRNGKey(0), np.ones((1, 4), np.float32))
    f32 = InferenceModel().load(m, v)
    bf16 = InferenceModel().load(m, v, dtype=jnp.bfloat16)
    x = np.random.default_rng(0).normal(size=(4, 4)).astype(np.float32)
    a, b = f32.predict(x), bf16.predict(x)
    np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)  # bf16 tolerance
    assert not np.allclose(a, b, rtol=1e-7, atol=0)  # actually lower precision


def test_update_model_hot_swap():
    import jax
    import analytics_zoo_tpu.nn as nn

    def make(bias_val):
        m = nn.Sequential([nn.Lambda(lambda x: x * 0.0 + bias_val)])
        v = m.init(jax.random.PRNGKey(0), np.ones((1, 4), np.float32))
        return InferenceModel().load(m, v)

    with ClusterServing(make(1.0), batch_size=4) as srv:
        q = InputQueue(srv.host, srv.port)
        out_q = OutputQueue(input_queue=q)
        uid = q.enqueue("a", t=np.ones(4, np.float32))
        before = out_q.query(uid, timeout=30)
        np.testing.assert_allclose(before, np.ones(4), rtol=1e-6)
        srv.update_model(make(2.0))  # hot-swap on the SAME connection
        uid2 = q.enqueue("b", t=np.ones(4, np.float32))
        after = out_q.query(uid2, timeout=30)
        np.testing.assert_allclose(after, np.full(4, 2.0), rtol=1e-6)
        q.close()


def test_inference_model_int8_weight_quantization():
    """Weight-only int8 serving (reference: doLoadOpenVINOInt8): large
    float params are stored int8 + per-channel scales (4x smaller), and
    predictions stay close to the f32 model."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving.inference_model import (InferenceModel,
                                                           _Q_MARKER)

    init_orca_context("local")
    model = nn.Sequential([nn.Dense(256, activation="relu"),
                           nn.Dense(128, activation="relu"),
                           nn.Dense(10)])
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 64)).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x))

    ref = InferenceModel().load(model, variables)
    q = InferenceModel().load(model, variables, dtype="int8")
    out_ref = np.asarray(ref.predict(x), np.float32)
    out_q = np.asarray(q.predict(x), np.float32)
    # int8 weights + bf16 activations: small but nonzero error
    denom = np.maximum(np.abs(out_ref), 1.0)
    assert np.max(np.abs(out_q - out_ref) / denom) < 0.08

    # big kernels really stored int8; small leaves (biases) stay float
    p = q._variables["params"]
    layer0 = p[next(iter(p))]  # first Dense layer's params
    k0 = layer0["kernel"]
    assert isinstance(k0, dict) and _Q_MARKER in k0
    assert k0["q"].dtype == jnp.int8
    assert not isinstance(layer0["bias"], dict)


def test_inference_model_int8_calibrated_activations():
    """Calibrated int8 (reference: OpenVINO INT8 calibration): a
    calibration batch freezes static per-tensor activation scales; Dense
    matmuls then run int8 x int8 -> int32 with per-channel rescale.
    Accuracy must stay close to f32, and the activation scales must
    actually come from the calibration pass."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context("local")
    model = nn.Sequential([nn.Dense(256, activation="relu"),
                           nn.Dense(128, activation="relu"),
                           nn.Dense(10)])
    rng = np.random.default_rng(3)
    calib = rng.normal(size=(32, 64)).astype(np.float32)
    x = rng.normal(size=(16, 64)).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(calib))

    ref = InferenceModel().load(model, variables)
    q = InferenceModel().load(model, variables, dtype="int8",
                              calibrate=calib)
    # one scale per Dense layer, recorded during the calibration forward
    assert q._quant_ctx is not None and len(q._quant_ctx.amax) == 3
    assert all(a > 0 for a in q._quant_ctx.amax.values())
    out_ref = np.asarray(ref.predict(x), np.float32)
    out_q = np.asarray(q.predict(x), np.float32)
    # int8 weights AND int8 activations: bounded accuracy delta vs f32
    denom = np.maximum(np.abs(out_ref), 1.0)
    assert np.max(np.abs(out_q - out_ref) / denom) < 0.15
    # ranking (the serving-relevant signal) preserved on most rows
    agree = np.mean(out_q.argmax(1) == out_ref.argmax(1))
    assert agree >= 0.8


def test_inference_model_int8_calibrated_with_lstm():
    """Regression (r4 review): calibrated int8 must leave NON-Dense 2-D
    kernels (LSTM input/recurrent kernels) dequantized — only nn.Dense
    can consume the int8 dict form."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context("local")
    model = nn.Sequential([nn.LSTM(64), nn.Dense(16, activation="relu"),
                           nn.Dense(4)])
    rng = np.random.default_rng(5)
    calib = rng.normal(size=(8, 12, 16)).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(calib))
    ref = InferenceModel().load(model, variables)
    q = InferenceModel().load(model, variables, dtype="int8",
                              calibrate=calib)
    x = rng.normal(size=(4, 12, 16)).astype(np.float32)
    out_ref = np.asarray(ref.predict(x), np.float32)
    out_q = np.asarray(q.predict(x), np.float32)  # must not crash
    denom = np.maximum(np.abs(out_ref), 1.0)
    assert np.max(np.abs(out_q - out_ref) / denom) < 0.2


def test_inference_model_reload_and_int8_dtype_spellings():
    """Regression (r3 review): reloading clears stale executables, and
    jnp.int8/np.int8 route to weight-only quantization (NOT a float->int
    cast that zeroes weights)."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving import InferenceModel

    init_orca_context("local")
    model = nn.Sequential([nn.Dense(128, activation="relu"),
                           nn.Dense(4)])
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 64)).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x))

    im = InferenceModel()
    im.load(model, variables)
    ref = np.asarray(im.predict(x), np.float32)
    # reload with a different variable STRUCTURE (int8 markers) — must
    # recompile, not crash on the stale executable
    im.load(model, variables, dtype=jnp.int8)
    out = np.asarray(im.predict(x), np.float32)
    assert not np.allclose(out, 0.0)  # int8 CAST would zero the weights
    denom = np.maximum(np.abs(ref), 1.0)
    assert np.max(np.abs(out - ref) / denom) < 0.08


def test_calibrate_without_int8_raises():
    """Regression (r4 review): a calibration batch with a non-int8 dtype
    must error, not be silently ignored."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving.inference_model import InferenceModel
    init_orca_context("local")
    m = nn.Sequential([nn.Dense(4)])
    x = np.zeros((2, 3), np.float32)
    v = m.init(jax.random.PRNGKey(0), jnp.asarray(x))
    with pytest.raises(ValueError, match="calibrate"):
        InferenceModel().load(m, v, calibrate=x)
    with pytest.raises(ValueError, match="calibrate"):
        InferenceModel().load(m, v, dtype=jnp.bfloat16, calibrate=x)


def test_calibrator_rejects_traced_forward():
    """Regression (r4 advisor): running the calibration forward under
    jit must fail with an actionable message, not an opaque
    TracerError deep inside float()."""
    import jax
    import jax.numpy as jnp
    from analytics_zoo_tpu.nn.quant import Calibrator

    calib = Calibrator()

    def f(x):
        calib.observe(("dense",), x)
        return x

    with pytest.raises(RuntimeError, match="UNJITTED"):
        jax.jit(f)(jnp.ones((2, 2)))


def test_inference_model_int8_calibrated_conv():
    """Calibrated int8 for CNNs (reference: OpenVINO INT8 calibrated
    whole CNNs): plain Conv2D inputs get static activation scales and
    run as int8 x int8 -> int32 convs; accuracy stays bounded vs f32 and
    the conv kernels really stay int8 through the serving path."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context("local")
    model = nn.Sequential([
        nn.Conv2D(32, 3, activation="relu"),
        nn.Conv2D(64, 3, strides=2, activation="relu"),
        nn.GlobalAveragePooling2D(),
        nn.Dense(10)])
    rng = np.random.default_rng(7)
    calib = rng.normal(size=(16, 16, 16, 3)).astype(np.float32)
    x = rng.normal(size=(8, 16, 16, 3)).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(calib))

    ref = InferenceModel().load(model, variables)
    q = InferenceModel().load(model, variables, dtype="int8",
                              calibrate=calib)
    # both convs AND the dense observed during calibration
    assert q._quant_ctx is not None and len(q._quant_ctx.amax) == 3
    out_ref = np.asarray(ref.predict(x), np.float32)
    out_q = np.asarray(q.predict(x), np.float32)
    denom = np.maximum(np.abs(out_ref), 1.0)
    assert np.max(np.abs(out_q - out_ref) / denom) < 0.2
    agree = np.mean(out_q.argmax(1) == out_ref.argmax(1))
    assert agree >= 0.75, agree


def test_ws_conv_stays_weight_only_under_calibration():
    """ScaledWSConv2D must NOT take the activation-quantized path (its
    weight standardization needs the float kernel): calibration must
    skip it and serving must still produce finite, close-to-f32 output."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context("local")
    # kernel 3*3*24*64 = 13,824 elements: ABOVE _Q_MIN_SIZE, so it
    # really is stored int8 and the WS conv must dequantize the dict
    # (a sub-threshold kernel would stay float and test nothing)
    model = nn.Sequential([
        nn.ScaledWSConv2D(64, 3, activation="relu"),
        nn.GlobalAveragePooling2D(),
        nn.Dense(8)])
    rng = np.random.default_rng(8)
    calib = rng.normal(size=(8, 12, 12, 24)).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(calib))
    q = InferenceModel().load(model, variables, dtype="int8",
                              calibrate=calib)
    # only the Dense observed — the WS conv opted out
    assert len(q._quant_ctx.amax) == 1
    ref = InferenceModel().load(model, variables)
    out_q = np.asarray(q.predict(calib), np.float32)
    out_ref = np.asarray(ref.predict(calib), np.float32)
    assert np.all(np.isfinite(out_q))
    denom = np.maximum(np.abs(out_ref), 1.0)
    assert np.max(np.abs(out_q - out_ref) / denom) < 0.2


# -- pipelined hot path (assembly → inference workers → reply writers) --------

class _PipeModel:
    """Stub with declared concurrency for pipelined-server tests: doubles
    its input, counts rows actually inferred, optional per-batch delay."""

    concurrent_num = 4

    def __init__(self, delay: float = 0.0):
        self.delay = delay
        self.calls = []
        self._lock = threading.Lock()

    def predict(self, x):
        if self.delay:
            time.sleep(self.delay)
        with self._lock:
            self.calls.append(np.asarray(x).shape[0])
        return np.asarray(x) * 2.0

    @property
    def rows_seen(self) -> int:
        with self._lock:
            return sum(self.calls)


def test_pipelined_mixed_shape_concurrent_clients():
    """inference_workers=2: concurrent clients with two feature shapes all
    get their own (correct) answer — shape groups may infer concurrently
    on different workers, replies still key by uuid."""
    with ClusterServing(_PipeModel(), batch_size=8, batch_timeout_ms=10,
                        inference_workers=2) as srv:
        assert srv.inference_workers == 2
        results, errors = {}, []

        def client(i):
            try:
                iq = InputQueue(srv.host, srv.port)
                oq = OutputQueue(input_queue=iq)
                shape = (4,) if i % 2 else (7,)
                x = np.full(shape, float(i), np.float32)
                uid = iq.enqueue(f"c{i}", t=x)
                results[i] = (shape, oq.query(uid, timeout=30.0))
                iq.close()
            except Exception as e:  # noqa: BLE001
                errors.append((i, e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
        assert len(results) == 16
        for i, (shape, out) in results.items():
            assert out.shape == shape
            np.testing.assert_allclose(out, np.full(shape, 2.0 * i),
                                       rtol=1e-6)
        s = srv.stats()
    assert s["requests"] == 16
    assert s["requests"] == s["replies"] + s["errors"] + s["pending"]


def test_stats_invariant_under_two_workers():
    """requests == replies + errors + pending must survive the pipelined
    restructure with concurrent inference workers."""
    with ClusterServing(_PipeModel(), batch_size=4, batch_timeout_ms=5,
                        inference_workers=2) as srv:
        iq = InputQueue(srv.host, srv.port)
        oq = OutputQueue(input_queue=iq)
        uids = [iq.enqueue(f"i{k}", t=np.full((6,), float(k), np.float32))
                for k in range(20)]
        for uid in uids:
            assert oq.query(uid, timeout=30.0) is not None
        s = srv.stats()
        iq.close()
    assert s["requests"] == 20 and s["pending"] == 0
    assert s["requests"] == s["replies"] + s["errors"] + s["pending"]
    assert s["inference_workers"] == 2


def test_slow_reading_client_does_not_stall_inference():
    """A client that stops reading its replies (tiny receive buffer, big
    tensors) blocks only its own connection's reply writer: other
    clients' requests keep flowing through assembly → inference → reply,
    and the slow client's own rows still get INFERRED (replies parked in
    its writer queue), because sendall no longer runs on the batcher."""
    import socket
    model = _PipeModel()
    rows = 16
    big = np.ones((262144,), np.float32)  # 1 MiB per request/reply
    with ClusterServing(model, batch_size=2, batch_timeout_ms=2,
                        inference_workers=2) as srv:
        slow = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        # shrink the receive window BEFORE connect so the server-side
        # sendall hits backpressure after a few replies
        slow.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 16384)
        slow.connect((srv.host, srv.port))
        try:
            for i in range(rows):
                protocol.send_frame(slow,
                                    protocol.encode({"uuid": f"slow-{i}"},
                                                    big))
            # ... and never read a single reply.
            # meanwhile a well-behaved client must round-trip promptly
            iq = InputQueue(srv.host, srv.port)
            oq = OutputQueue(input_queue=iq)
            t0 = time.monotonic()
            for k in range(8):
                uid = iq.enqueue(f"fast-{k}",
                                 t=np.full((8,), float(k), np.float32))
                out = oq.query(uid, timeout=30.0)
                np.testing.assert_allclose(out, np.full((8,), 2.0 * k),
                                           rtol=1e-6)
            fast_elapsed = time.monotonic() - t0
            assert fast_elapsed < 20.0
            # the slow client's rows were all inferred too — its replies
            # are queued/blocked in ITS writer, not holding the model
            deadline = time.monotonic() + 20.0
            while (model.rows_seen < rows + 8
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert model.rows_seen == rows + 8, model.rows_seen
            # counters are final pre-send: replies counts the stuck ones
            s = srv.stats()
            assert s["replies"] == rows + 8
            assert s["requests"] == s["replies"] + s["errors"] + s["pending"]
            iq.close()
        finally:
            slow.close()


def test_stop_drains_assembled_batches_in_internal_queue():
    """stop() with work at EVERY pipeline depth: the in-flight batch
    finishes, batches waiting in the internal assembled-batch queue and
    requests still in the native queue all get the explicit
    "server shutting down" reply — no hung queries, invariant intact."""
    from analytics_zoo_tpu.serving.client import RetryPolicy
    model = _PipeModel(delay=0.3)
    srv = ClusterServing(model, batch_size=1, batch_timeout_ms=1,
                         inference_workers=1).start()
    iq = InputQueue(srv.host, srv.port, retry=RetryPolicy(max_attempts=1))
    oq = OutputQueue(input_queue=iq)
    x = np.arange(4, dtype=np.float32)
    uids = [iq.enqueue(f"d{i}", t=x) for i in range(6)]
    time.sleep(0.15)  # first batch is inside the model; rest are staged
    outcomes = {}

    def drain_query(uid):
        try:
            outcomes[uid] = ("ok", oq.query(uid, timeout=15.0))
        except RuntimeError as e:
            outcomes[uid] = ("error", str(e))

    threads = [threading.Thread(target=drain_query, args=(u,))
               for u in uids]
    for t in threads:
        t.start()
    srv.stop()
    for t in threads:
        t.join(timeout=20)
    assert not any(t.is_alive() for t in threads), "hung query() calls"
    assert len(outcomes) == 6
    served = [u for u, (kind, _) in outcomes.items() if kind == "ok"]
    drained = [u for u, (kind, msg) in outcomes.items()
               if kind == "error" and "server shutting down" in msg]
    assert len(served) + len(drained) == 6, outcomes
    # inference_workers=1 and one batch takes 0.3s: most of the queue
    # (native + internal assembled) must have been drained, not served
    assert len(drained) >= 2
    s = srv.stats()
    assert s["drained"] == len(drained)
    assert s["requests"] == s["replies"] + s["errors"] + s["pending"] == 6
    iq.close()


def test_batch_error_reply_carries_trace_id():
    """A whole-batch inference failure must include the trace id in its
    error reply so traced clients can correlate the failure."""
    import socket

    class _Boom:
        concurrent_num = 2

        def predict(self, x):
            raise ValueError("boom-batch")

    with ClusterServing(_Boom(), batch_size=2) as srv:
        raw = socket.create_connection((srv.host, srv.port), timeout=10)
        try:
            protocol.send_frame(raw, protocol.encode(
                {"uuid": "traced-1", "trace": "feedbeeffeedbeef"},
                np.ones((4,), np.float32)))
            header, _ = protocol.decode(protocol.recv_frame(raw))
            assert header["uuid"] == "traced-1"
            assert "boom-batch" in header["error"]
            assert header["trace"] == "feedbeeffeedbeef"
        finally:
            raw.close()


def test_staging_buffers_are_reused_across_batches():
    """Batch assembly stages rows into a pooled per-shape buffer instead
    of a fresh np.stack: after sequential batches of one shape, the pool
    holds at most `staging_pool` buffers and results stay correct."""
    model = _PipeModel()
    with ClusterServing(model, batch_size=4, batch_timeout_ms=2,
                        inference_workers=1, staging_pool=2) as srv:
        iq = InputQueue(srv.host, srv.port)
        oq = OutputQueue(input_queue=iq)
        for round_i in range(6):
            uid = iq.enqueue(f"r{round_i}",
                             t=np.full((5,), float(round_i), np.float32))
            out = oq.query(uid, timeout=30.0)
            np.testing.assert_allclose(out, np.full((5,), 2.0 * round_i),
                                       rtol=1e-6)
        key = ((5,), "float32")
        with srv._staging_lock:
            pool = list(srv._staging.get(key, []))
        assert 1 <= len(pool) <= 2  # reused, bounded by staging_pool
        iq.close()


def test_worker_reshed_keeps_survivor_rows_aligned():
    """Regression (review): a deadline that expires while a batch waits
    in the INTERNAL queue sheds that row at the worker — the surviving
    request must still get the prediction for ITS OWN input, not its
    shed neighbor's (the batch is re-staged after the shed)."""
    from analytics_zoo_tpu.serving.client import RetryPolicy
    model = _PipeModel(delay=0.8)
    with ClusterServing(model, batch_size=2, batch_timeout_ms=50,
                        inference_workers=1) as srv:
        iq = InputQueue(srv.host, srv.port,
                        retry=RetryPolicy(max_attempts=1))
        oq = OutputQueue(input_queue=iq)
        # batch 1 fills immediately and occupies the single worker 0.8s
        x1 = iq.enqueue("x1", t=np.full((4,), 10.0, np.float32))
        x2 = iq.enqueue("x2", t=np.full((4,), 20.0, np.float32))
        time.sleep(0.1)
        # batch 2 = [doomed, survivor] waits in the internal queue while
        # the worker is busy; doomed's 0.25s budget expires there
        doomed = iq.enqueue("doomed", deadline=0.25,
                            t=np.full((4,), 30.0, np.float32))
        survivor = iq.enqueue("survivor",
                              t=np.full((4,), 40.0, np.float32))
        with pytest.raises(RuntimeError, match="deadline exceeded"):
            oq.query(doomed, timeout=20.0)
        out = oq.query(survivor, timeout=20.0)
        # misaligned zip would deliver 2*30 (the shed row) here
        np.testing.assert_allclose(out, np.full((4,), 80.0), rtol=1e-6)
        assert oq.query(x1, timeout=20.0) is not None
        assert oq.query(x2, timeout=20.0) is not None
        # the shed row never ran inference: 2 (first batch) + 1 survivor
        assert model.rows_seen == 3
        s = srv.stats()
        assert s["shed"] == 1
        assert s["requests"] == s["replies"] + s["errors"] + s["pending"]
        iq.close()


def test_passthrough_model_replies_do_not_alias_staging_buffer():
    """Regression (review): a model returning (a view of) its input must
    not leave reply rows aliasing the pooled staging buffer — later
    batches would overwrite queued replies.  Interleaved same-shape
    requests with distinct payloads must each get their own echo."""

    class _Identity:
        concurrent_num = 2

        def predict(self, x):
            return x  # returns the staging-buffer view itself

    with ClusterServing(_Identity(), batch_size=4, batch_timeout_ms=1,
                        inference_workers=2, staging_pool=1) as srv:
        iq = InputQueue(srv.host, srv.port)
        oq = OutputQueue(input_queue=iq)
        uids = [(i, iq.enqueue(f"e{i}",
                               t=np.full((16,), float(i), np.float32)))
                for i in range(32)]
        for i, uid in uids:
            out = oq.query(uid, timeout=30.0)
            np.testing.assert_array_equal(out, np.full((16,), float(i),
                                                       np.float32))
        iq.close()


def test_failed_batch_does_not_double_release_staging_buffer():
    """Regression (review): an exception AFTER the success-path buffer
    release (e.g. a 0-d model output breaking the reply zip) must not
    put the same buffer into the pool twice."""

    class _ZeroD:
        concurrent_num = 2

        def __init__(self):
            self.fail = True

        def predict(self, x):
            if self.fail:
                return np.float32(3.0)  # zip() over 0-d raises
            return np.asarray(x) * 2.0

    model = _ZeroD()
    with ClusterServing(model, batch_size=2, inference_workers=1,
                        staging_pool=4) as srv:
        iq = InputQueue(srv.host, srv.port)
        oq = OutputQueue(input_queue=iq)
        with pytest.raises(RuntimeError):
            oq.query(iq.enqueue("bad", t=np.ones((4,), np.float32)),
                     timeout=20.0)
        model.fail = False
        out = oq.query(iq.enqueue("good", t=np.ones((4,), np.float32)),
                       timeout=20.0)
        np.testing.assert_allclose(out, np.full((4,), 2.0), rtol=1e-6)
        key = ((4,), "float32")
        with srv._staging_lock:
            pool = list(srv._staging.get(key, []))
        assert len(set(map(id, pool))) == len(pool), "duplicate buffer"
        iq.close()


def test_writer_overflow_drops_dead_client_not_workers(monkeypatch):
    """Regression (review): a client whose reply queue stays full past
    the push grace is DROPPED — the shared inference workers (and a
    later stop()) must never block forever on one dead connection."""
    import socket
    from analytics_zoo_tpu.serving.server import _ConnWriter
    monkeypatch.setattr(_ConnWriter, "MAX_ITEMS", 8)
    monkeypatch.setattr(_ConnWriter, "PUSH_GRACE_S", 0.2)
    model = _PipeModel()
    with ClusterServing(model, batch_size=4, batch_timeout_ms=1,
                        inference_workers=2) as srv:
        dead = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        dead.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        dead.connect((srv.host, srv.port))
        big = np.ones((65536,), np.float32)  # 256 KiB replies
        try:
            for i in range(24):  # >> queue bound + socket buffers
                protocol.send_frame(dead,
                                    protocol.encode({"uuid": f"n{i}"},
                                                    big))
            # a healthy client keeps round-tripping while (and after)
            # the dead one overflows and gets dropped
            iq = InputQueue(srv.host, srv.port)
            oq = OutputQueue(input_queue=iq)
            for k in range(6):
                uid = iq.enqueue(f"h{k}",
                                 t=np.full((8,), float(k), np.float32))
                out = oq.query(uid, timeout=30.0)
                np.testing.assert_allclose(out, np.full((8,), 2.0 * k),
                                           rtol=1e-6)
                time.sleep(0.1)
            iq.close()
        finally:
            dead.close()
        srv.stop()  # must return promptly, not deadlock on the drain
    s = srv.stats()
    assert s["requests"] == s["replies"] + s["errors"] + s["pending"]


# -- zero-copy protocol --------------------------------------------------------

def test_encode_parts_matches_encode_and_decodes():
    header = {"uuid": "zc-1", "trace": "0123456789abcdef"}
    arr = np.arange(24, dtype=np.float32).reshape(4, 6)
    joined = b"".join(protocol.encode_parts(header, arr))
    assert joined == protocol.encode(header, arr)
    got_header, got = protocol.decode(bytearray(joined[4:]))
    assert got_header["uuid"] == "zc-1"
    np.testing.assert_array_equal(got, arr)
    # non-contiguous input still encodes its logical content
    nc = np.arange(32, dtype=np.float32).reshape(8, 4)[::2]
    _, got_nc = protocol.decode(
        bytearray(b"".join(protocol.encode_parts({"uuid": "z"}, nc))[4:]))
    np.testing.assert_array_equal(got_nc, nc)


def test_send_frame_parts_handles_partial_sends():
    """Scatter-gather send must survive partial sendmsg returns (small
    socket buffers + a large tensor): the peer reassembles the exact
    frame."""
    import socket
    a, b = socket.socketpair()
    try:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)
        arr = np.random.default_rng(0).normal(
            size=(1024, 64)).astype(np.float32)  # 256 KiB payload
        parts = protocol.encode_parts({"uuid": "big"}, arr)
        sender = threading.Thread(
            target=protocol.send_frame_parts, args=(a, parts))
        sender.start()
        frame = protocol.recv_frame(b)
        sender.join(timeout=10)
        assert not sender.is_alive()
        header, got = protocol.decode(frame)
        assert header["uuid"] == "big"
        np.testing.assert_array_equal(got, arr)
    finally:
        a.close()
        b.close()


def test_recv_frame_rejects_oversized_length(monkeypatch):
    """SATELLITE: a corrupt/malicious 4-byte length must be rejected
    BEFORE any allocation (configurable MAX_FRAME_BYTES), not answered
    with a multi-GiB bytearray attempt."""
    import socket
    import struct
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack(">I", protocol.MAX_FRAME_BYTES + 1))
        with pytest.raises(ValueError, match="MAX_FRAME_BYTES"):
            protocol.recv_frame(b)
    finally:
        a.close()
        b.close()
    # the bound is configurable: a legitimate frame over a lowered bound
    # is rejected the same way
    monkeypatch.setattr(protocol, "MAX_FRAME_BYTES", 64)
    a, b = socket.socketpair()
    try:
        a.sendall(protocol.encode({"uuid": "x"},
                                  np.zeros((64,), np.float32)))
        with pytest.raises(ValueError, match="MAX_FRAME_BYTES"):
            protocol.recv_frame(b)
    finally:
        a.close()
        b.close()


def test_server_survives_oversized_frame_then_serves(inference_model):
    """An oversized length prefix kills that connection only; the server
    keeps serving well-formed clients."""
    import socket
    import struct
    with ClusterServing(inference_model, batch_size=2) as srv:
        raw = socket.create_connection((srv.host, srv.port), timeout=10)
        try:
            raw.sendall(struct.pack(">I", protocol.MAX_FRAME_BYTES + 7))
            raw.settimeout(10)
            assert raw.recv(1) == b""  # server closed the connection
        finally:
            raw.close()
        iq = InputQueue(srv.host, srv.port)
        oq = OutputQueue(input_queue=iq)
        uid = iq.enqueue("ok", t=np.ones(4, np.float32))
        assert oq.query(uid, timeout=20.0) is not None
        iq.close()


def test_save_load_executables_roundtrip(tmp_path):
    """Serialized AOT artifacts (reference: OpenVINO IR) round-trip: a
    fresh InferenceModel loads them, skips tracing, and predicts the
    same values; a config mismatch (different precision) ignores them."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context("local")
    model = nn.Sequential([nn.Dense(32, activation="relu"), nn.Dense(4)])
    rng = np.random.default_rng(9)
    x = rng.normal(size=(4, 8)).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x))

    src = InferenceModel().load(model, variables)
    want = np.asarray(src.predict(x))
    n = src.save_executables(str(tmp_path / "aot"))
    assert n == 1  # one (shape, dtype) bucket compiled

    dst = InferenceModel().load(model, variables)
    assert dst.load_executables(str(tmp_path / "aot")) == 1
    got = np.asarray(dst.predict(x))  # served via the deserialized artifact
    np.testing.assert_allclose(got, want, rtol=1e-6)

    # precision mismatch -> artifacts ignored, fresh compile still works
    other = InferenceModel().load(model, variables, dtype=jnp.bfloat16)
    assert other.load_executables(str(tmp_path / "aot")) == 0
    assert np.asarray(other.predict(x)).shape == want.shape


def test_load_executables_compiles_once_no_per_call_retrace(tmp_path):
    """A warm-reload artifact must dispatch a cached executable, not
    re-trace per call: load_executables wraps the deserialized
    ``exp.call`` in an AOT-compiled ``jax.stages.Compiled`` ONCE at load
    time, without counting into ``compile_count`` (the hot-swap
    acceptance treats artifact loads as free)."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context("local")
    model = nn.Sequential([nn.Dense(32, activation="relu"), nn.Dense(4)])
    rng = np.random.default_rng(11)
    x = rng.normal(size=(4, 8)).astype(np.float32)
    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(x))

    src = InferenceModel().load(model, variables)
    want = np.asarray(src.predict(x))
    assert src.save_executables(str(tmp_path / "aot")) == 1

    dst = InferenceModel().load(model, variables)
    assert dst.load_executables(str(tmp_path / "aot")) == 1
    assert dst.compile_count == 0  # artifact loads are not fresh compiles
    fns = list(dst._compiled.values())
    assert len(fns) == 1
    # the load-time wrap: a Compiled stage, not the raw re-tracing
    # exp.call bound method
    assert isinstance(fns[0], jax.stages.Compiled)
    got = np.asarray(dst.predict(x))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # repeated predicts keep dispatching the SAME cached executable
    assert dst._compiled[next(iter(dst._compiled))] is fns[0]
    assert dst.compile_count == 0


def test_load_executables_rejects_stale_model_code(tmp_path):
    """A model-code edit that leaves the variable tree identical must
    NOT silently serve the stale artifact: the traced-computation hash
    (manifest "jaxpr") catches it; verify=False trusts the artifact."""
    import jax
    import jax.numpy as jnp
    import analytics_zoo_tpu.nn as nn
    from analytics_zoo_tpu.serving.inference_model import InferenceModel

    init_orca_context("local")
    rng = np.random.default_rng(10)
    x = rng.normal(size=(4, 8)).astype(np.float32)
    relu_net = nn.Sequential([nn.Dense(16, activation="relu"),
                              nn.Dense(4)])
    gelu_net = nn.Sequential([nn.Dense(16, activation="gelu"),
                              nn.Dense(4)])  # same param tree, new math
    variables = relu_net.init(jax.random.PRNGKey(0), jnp.asarray(x))

    src = InferenceModel().load(relu_net, variables)
    src.predict(x)
    assert src.save_executables(str(tmp_path / "aot")) == 1

    stale = InferenceModel().load(gelu_net, variables)
    assert stale.load_executables(str(tmp_path / "aot")) == 0
    # and the unverified fast path loads it (caller's responsibility)
    assert stale.load_executables(str(tmp_path / "aot"),
                                  verify=False) == 1
