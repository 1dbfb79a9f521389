"""The bench driver's transient-failure handling and exit contract.

Tests the retry classification and the bounded-retry loop with FORCED
failures — no device work involved — and that bench.py says no rather
than measure the wrong thing: a non-TPU platform outside --smoke, a
Mosaic compile failure, a phase that recorded an error.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import bench  # noqa: E402  (repo-root module)


class FakeJaxRuntimeError(RuntimeError):
    """Stands in for jax's JaxRuntimeError (matched by type NAME)."""


FakeJaxRuntimeError.__name__ = "JaxRuntimeError"


def _unavailable_error():
    return FakeJaxRuntimeError("UNAVAILABLE: Socket closed")


class TestIsTransient:
    def test_unavailable_is_transient(self):
        assert bench.is_transient(_unavailable_error())

    def test_deadline_exceeded_is_transient(self):
        assert bench.is_transient(
            FakeJaxRuntimeError("DEADLINE_EXCEEDED: connection reset"))

    def test_mosaic_compile_failure_is_not(self):
        # arrives as INTERNAL; rebuilding an 8B engine three times would
        # fail three times
        assert not bench.is_transient(FakeJaxRuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel: Ran out of "
            "memory in memory space vmem"))

    def test_plain_runtime_error_is_not(self):
        # a non-jax RuntimeError with a scary message is NOT retried
        assert not bench.is_transient(
            RuntimeError("UNAVAILABLE: Socket closed"))

    def test_jax_shape_error_is_not(self):
        assert not bench.is_transient(
            FakeJaxRuntimeError("mismatched shapes for dot_general"))

    def test_value_error_is_not(self):
        assert not bench.is_transient(ValueError("INTERNAL"))


class TestWithRetries:
    def test_success_passes_through(self):
        errors = []
        assert bench.with_retries("p", lambda: 42, errors) == 42
        assert errors == []

    def test_transient_failure_then_success(self):
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise _unavailable_error()
            return "ok"

        errors = []
        out = bench.with_retries("engine", flaky, errors, attempts=3,
                                 sleep=lambda s: None)
        assert out == "ok"
        assert len(calls) == 3
        assert len(errors) == 2
        assert all(e.startswith("engine: attempt") for e in errors)

    def test_exhausted_retries_return_none_with_errors(self):
        def always_fails():
            raise _unavailable_error()

        errors = []
        out = bench.with_retries("engine", always_fails, errors, attempts=3,
                                 sleep=lambda s: None)
        assert out is None
        assert len(errors) == 3

    def test_non_transient_fails_immediately(self):
        calls = []

        def buggy():
            calls.append(1)
            raise ValueError("bad shape")

        errors = []
        out = bench.with_retries("engine", buggy, errors, attempts=3,
                                 sleep=lambda s: None)
        assert out is None
        assert len(calls) == 1  # no retry on the bug class
        assert "ValueError" in errors[0]

    def test_backoff_is_bounded(self):
        slept = []

        def always_fails():
            raise _unavailable_error()

        bench.with_retries("p", always_fails, [], attempts=3,
                           backoff_s=1.0, sleep=slept.append)
        assert slept == [1.0, 2.0]  # attempts-1 sleeps, linear backoff


class TestExitContract:
    def test_exit_status_is_nonzero_when_a_phase_recorded_an_error(self):
        assert bench.exit_status(True, []) == 0
        assert bench.exit_status(True, ["gateway: attempt 1: boom"]) == 1
        assert bench.exit_status(False, []) == 1

    def test_outside_smoke_a_cpu_platform_is_an_error(self):
        """No CPU fallback: one {"error": ...} line and a non-zero exit,
        before anything is built or timed."""
        import json
        import os
        import subprocess

        env = dict(os.environ, BENCH_MODEL="debug-tiny", JAX_PLATFORMS="cpu")
        env.pop("LLMK_TEST_TPU", None)
        out = subprocess.run(
            [sys.executable, str(pathlib.Path(bench.__file__))],
            capture_output=True, text=True, timeout=300, env=env)
        assert out.returncode != 0
        lines = out.stdout.strip().splitlines()
        assert len(lines) == 1
        assert "platform='cpu'" in json.loads(lines[0])["error"]["message"]


class TestRetryAfter:
    """ISSUE 7: the bench HTTP client honors the server's Retry-After
    hint on 429/503 instead of blind immediate retry."""

    @staticmethod
    def _scripted(responses):
        it = iter(responses)

        def send():
            return next(it)
        return send

    def test_server_hint_honored_exactly(self):
        slept = []
        send = self._scripted([
            (429, {"Retry-After": "7"}, b"full"),
            (503, {"retry-after": "2.5"}, b"draining"),  # case-insensitive
            (200, {}, b"ok"),
        ])
        status, _, data = bench.request_with_retry_after(
            send, attempts=4, backoff_s=0.2, sleep=slept.append)
        assert (status, data) == (200, b"ok")
        assert slept == [7.0, 2.5]  # the hints, not the backoff schedule

    def test_missing_header_falls_back_to_capped_backoff(self):
        slept = []
        send = self._scripted([(503, {}, b"")] * 5)
        status, _, _ = bench.request_with_retry_after(
            send, attempts=5, backoff_s=1.0, max_backoff_s=4.0,
            sleep=slept.append)
        assert status == 503            # last attempt returned as-is
        assert slept == [1.0, 2.0, 4.0, 4.0]  # exponential, capped

    def test_malformed_hint_falls_back_to_backoff(self):
        slept = []
        send = self._scripted([
            (429, {"Retry-After": "soon"}, b""),
            (200, {}, b"ok"),
        ])
        status, _, _ = bench.request_with_retry_after(
            send, attempts=2, backoff_s=0.3, sleep=slept.append)
        assert status == 200
        assert slept == [0.3]

    def test_negative_hint_clamped_to_zero(self):
        slept = []
        send = self._scripted([(503, {"Retry-After": "-3"}, b""),
                               (200, {}, b"ok")])
        bench.request_with_retry_after(send, attempts=2, sleep=slept.append)
        assert slept == [0.0]

    def test_success_and_hard_errors_return_immediately(self):
        slept = []
        send = self._scripted([(200, {"Retry-After": "9"}, b"ok")])
        status, _, _ = bench.request_with_retry_after(
            send, attempts=5, sleep=slept.append)
        assert status == 200 and slept == []
        send = self._scripted([(404, {}, b"nope")])
        status, _, _ = bench.request_with_retry_after(
            send, attempts=5, sleep=slept.append)
        assert status == 404 and slept == []  # 4xx bugs are not retried


class TestPartialEmission:
    def test_smoke_mode_emits_json_and_names_router(self):
        """``bench.py --smoke`` (the CI gate) must exit 0 with one parseable
        JSON line; the gateway traffic rides the native llkt-router, built
        from the tracked sources (a failed build fails the phase)."""
        import json
        import os
        import subprocess

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("LLMK_TEST_TPU", None)
        env.pop("LLMK_BENCH_SMOKE", None)
        out = subprocess.run(
            [sys.executable, str(pathlib.Path(bench.__file__)), "--smoke"],
            capture_output=True, text=True, timeout=600, env=env)
        line = out.stdout.strip().splitlines()[-1]
        data = json.loads(line)
        assert data["smoke"] is True
        assert data["value"] > 0
        # ISSUE 7: the spike scenario rides the smoke pass — scale-from-
        # zero wake + one preempted replica, with zero dropped streams
        assert data["dropped_streams"] == 0
        assert data["spike_completed_streams"] > 0
        assert data["spike_preempted_replicas"] == 1
        assert data["spike_cold_start_s"].get("ready", 0) > 0
        # ISSUE 10: the fairness scenario too — the noisy batch tenant
        # absorbs the sheds, interactive TTFT stays bounded, nobody
        # starves, and the forced brownout sheds with the overload body
        assert data["fairness_ttft_ratio"] < 2.0
        assert data["fairness_shed_noisy_fraction"] >= 0.9
        assert data["fairness_min_tenant_completed"] >= 1
        assert data["fairness_overload_shed_ok"] is True
        # ISSUE 12: the speculative-decoding scenario — greedy outputs
        # bit-identical with speculation on/off, drafts accepted on
        # lookup-friendly traffic, dispatch rate beating the plain fused
        # window's post-pipeline 1/(K-1)
        assert data["spec_parity_ok"] is True
        assert data["spec_accept_ratio"] > 0
        assert data["spec_dispatches_per_token"] < 0.286
        # ISSUE 16: the disaggregated prefill/decode scenario — streams
        # bit-identical to colocated, both fault waves absorbed with zero
        # client-visible drops, and every handoff outcome accounted for
        assert data["disagg_parity_ok"] is True
        assert data["disagg_dropped_streams"] == 0
        assert data["disagg_handoff_ok"] >= 1
        assert data["disagg_handoff_reprefill"] >= 1
        assert data["disagg_handoff_fallback"] >= 1
        assert data["disagg_decode_idle_frac"] < data["colocated_decode_idle_frac"]
        assert data["gateway_router"] == "native"
        assert "errors" not in data
        assert out.returncode == 0
