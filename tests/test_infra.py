import asyncio
import logging
import time

import pytest

from sentio_tpu.config import AuthConfig, CacheConfig
from sentio_tpu.infra.auth import JWT, AuthManager, hash_password, verify_password
from sentio_tpu.infra.caching import (
    AdaptiveStrategy,
    CacheManager,
    MemoryCache,
    NullL2Cache,
    SizeAwareStrategy,
)
from sentio_tpu.infra.exceptions import (
    AuthError,
    CircuitOpenError,
    ErrorCode,
    ErrorHandler,
    ForbiddenError,
    RateLimitError,
    SentioError,
    ValidationError,
)
from sentio_tpu.infra.resilience import (
    CircuitBreaker,
    CircuitState,
    FallbackResponseCache,
    LLMFallback,
    ResilientCall,
    RetryPolicy,
    embedding_fallback,
    with_retry,
)
from sentio_tpu.infra.security import (
    CSRFProtection,
    InputValidator,
    IPRateLimiter,
    LogSanitizer,
    sanitize_text,
)


class TestCircuitBreaker:
    def test_opens_after_threshold_and_recovers(self):
        breaker = CircuitBreaker(failure_threshold=3, recovery_timeout_s=0.05,
                                 success_threshold=1)

        def boom():
            raise RuntimeError("x")

        for _ in range(3):
            with pytest.raises(RuntimeError):
                breaker.call(boom)
        assert breaker.state == CircuitState.OPEN
        with pytest.raises(CircuitOpenError):
            breaker.call(lambda: "fine")
        time.sleep(0.06)
        assert breaker.call(lambda: "recovered") == "recovered"
        assert breaker.state == CircuitState.CLOSED

    def test_half_open_failure_reopens(self):
        breaker = CircuitBreaker(failure_threshold=1, recovery_timeout_s=0.02)
        with pytest.raises(RuntimeError):
            breaker.call(lambda: (_ for _ in ()).throw(RuntimeError()))
        time.sleep(0.03)
        with pytest.raises(RuntimeError):
            breaker.call(lambda: (_ for _ in ()).throw(RuntimeError()))
        assert breaker.state == CircuitState.OPEN

    def test_async_breaker(self):
        breaker = CircuitBreaker(failure_threshold=1, recovery_timeout_s=10)

        async def run():
            async def boom():
                raise ValueError("async fail")

            with pytest.raises(ValueError):
                await breaker.acall(boom)
            with pytest.raises(CircuitOpenError):
                await breaker.acall(boom)

        asyncio.run(run())
        assert breaker.health()["state"] == "open"


class TestRetry:
    def test_retries_then_succeeds(self):
        calls = {"n": 0}

        @with_retry(RetryPolicy(max_attempts=3, base_delay_s=0.001))
        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "done"

        assert flaky() == "done"
        assert calls["n"] == 3

    def test_exhaustion_raises_last(self):
        policy = RetryPolicy(max_attempts=2, base_delay_s=0.001)
        with pytest.raises(ValueError, match="always"):
            policy.run(lambda: (_ for _ in ()).throw(ValueError("always")))

    def test_nonpositive_max_attempts_raises_value_error(self):
        # used to fall off the loop and `raise None` (an opaque TypeError)
        policy = RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError, match="max_attempts"):
            policy.run(lambda: "never")
        with pytest.raises(ValueError, match="max_attempts"):
            asyncio.run(RetryPolicy(max_attempts=-1).arun(None))

    def test_injected_rng_makes_jitter_deterministic(self):
        import random as _random

        a = RetryPolicy(base_delay_s=0.1, rng=_random.Random(42))
        b = RetryPolicy(base_delay_s=0.1, rng=_random.Random(42))
        assert [a.delay(i) for i in range(4)] == [b.delay(i) for i in range(4)]

    def test_resilient_call_timeout(self):
        async def run():
            rc = ResilientCall("slow", timeout_s=0.02,
                               retry=RetryPolicy(max_attempts=1, base_delay_s=0.001))

            async def sleepy():
                await asyncio.sleep(1.0)

            from sentio_tpu.infra.exceptions import TimeoutError_

            with pytest.raises(TimeoutError_):
                await rc.execute(sleepy)

        asyncio.run(run())


class TestFallbacks:
    def test_response_cache_roundtrip(self, tmp_path):
        cache = FallbackResponseCache(cache_dir=str(tmp_path), ttl_s=100)
        assert cache.get("what is jax?") is None
        cache.put("what is jax?", "a library")
        assert cache.get("What is JAX?  ") == "a library"  # normalized key
        fresh = FallbackResponseCache(cache_dir=str(tmp_path), ttl_s=100)
        assert fresh.get("what is jax?") == "a library"  # disk persisted

    def test_response_cache_ttl(self, tmp_path):
        cache = FallbackResponseCache(cache_dir=str(tmp_path), ttl_s=0.01)
        cache.put("q", "a")
        time.sleep(0.02)
        assert cache.get("q") is None

    def test_expired_deletion_persists_to_disk(self, tmp_path):
        cache = FallbackResponseCache(cache_dir=str(tmp_path), ttl_s=0.01)
        cache.put("q", "a")
        time.sleep(0.02)
        assert cache.get("q") is None
        # a fresh instance loads from disk: the expired entry must NOT
        # resurrect (pre-fix, deletion only ever happened in memory)
        fresh = FallbackResponseCache(cache_dir=str(tmp_path), ttl_s=1e9)
        assert fresh.get("q") is None

    def test_max_entries_lru_cap(self, tmp_path):
        cache = FallbackResponseCache(cache_dir=str(tmp_path), ttl_s=0,
                                      max_entries=3)
        for i in range(6):
            cache.put(f"question {i}", f"answer {i}")
            time.sleep(0.002)  # distinct write stamps for eviction order
        # only the newest 3 survive, in memory AND on disk
        assert cache.get("question 0") is None
        assert cache.get("question 5") == "answer 5"
        fresh = FallbackResponseCache(cache_dir=str(tmp_path), ttl_s=0)
        assert len(fresh._store) <= 3
        assert fresh.get("question 5") == "answer 5"

    def test_eviction_is_recency_based_not_fifo(self, tmp_path):
        cache = FallbackResponseCache(cache_dir=str(tmp_path), ttl_s=0,
                                      max_entries=3)
        for i in range(3):
            cache.put(f"q{i}", f"a{i}")
            time.sleep(0.002)
        # touch the OLDEST-written entry, then overflow: the least recently
        # USED entry (q1) must go, and the hot q0 must survive
        assert cache.get("q0") == "a0"
        time.sleep(0.002)
        cache.put("q3", "a3")
        assert cache.get("q1") is None
        assert cache.get("q0") == "a0"

    def test_llm_fallback_templates(self):
        fb = LLMFallback(prompts_dir="prompts")
        assert "knowledge base" in fb.no_retrieval("my question")
        assert "unavailable" in fb.no_llm("some context")
        assert fb.apology()

    def test_embedding_fallback_deterministic_unit(self):
        import numpy as np

        a = embedding_fallback("hello", 32)
        b = embedding_fallback("HELLO", 32)
        assert a == b  # case-normalized
        assert abs(np.linalg.norm(a) - 1.0) < 1e-5


class TestCaching:
    def test_lru_eviction_order(self):
        cache = MemoryCache(max_entries=2)
        cache.set("a", 1)
        cache.set("b", 2)
        cache.get("a")  # refresh a
        cache.set("c", 3)  # evicts b
        assert cache.get("a") == 1
        assert cache.get("b") is None
        assert cache.get("c") == 3

    def test_ttl_and_cleanup(self):
        cache = MemoryCache(max_entries=10, default_ttl_s=0.01)
        cache.set("x", 1)
        time.sleep(0.02)
        assert cache.get("x") is None
        cache.set("y", 2, ttl_s=0.01)
        time.sleep(0.02)
        assert cache.cleanup_expired() == 1

    def test_pattern_clear(self):
        cache = MemoryCache()
        cache.set("query:a", 1)
        cache.set("query:b", 2)
        cache.set("emb:c", 3)
        assert cache.clear("query:*") == 2
        assert cache.get("emb:c") == 3

    def test_manager_typed_helpers(self):
        mgr = CacheManager(CacheConfig(backend="memory"))
        mgr.set_query_response("  What is JAX? ", {"answer": "lib"})
        assert mgr.get_query_response("what is jax?") == {"answer": "lib"}
        assert mgr.stats()["l1"]["entries"] == 1

    def test_manager_off_backend(self):
        mgr = CacheManager(CacheConfig(backend="off"))
        mgr.set("k", "v")
        assert mgr.get("k") is None

    def test_multi_tier_l2_promotion(self):
        class DictL2(NullL2Cache):
            def __init__(self):
                self.store = {}

            async def get(self, key):
                return self.store.get(key)

            async def set(self, key, value, ttl_s):
                self.store[key] = value

        async def run():
            l2 = DictL2()
            mgr = CacheManager(CacheConfig(backend="multi_tier"), l2=l2)
            await mgr.aset("k", "v")
            assert l2.store["k"] == "v"
            mgr.l1.clear()
            assert await mgr.aget("k") == "v"  # L2 hit
            assert mgr.l1.get("k") == "v"  # promoted to L1

        asyncio.run(run())

    def test_size_aware_strategy(self):
        s = SizeAwareStrategy(max_bytes=10)
        assert s.should_cache("k", "short") is True
        assert s.should_cache("k", "x" * 100) is False

    def test_adaptive_strategy_ttl_scales(self):
        s = AdaptiveStrategy(base_ttl_s=100)
        for _ in range(9):
            s.record("hot:q", hit=True)
        s.record("hot:q", hit=False)
        for _ in range(10):
            s.record("cold:q", hit=False)
        assert s.ttl_for("hot:x", 1) > s.ttl_for("cold:x", 1)


class TestAuth:
    def _mgr(self):
        return AuthManager(AuthConfig(enabled=True, jwt_secret="test-secret",
                                      max_failed_attempts=2, lockout_s=0.05,
                                      min_password_len=8))

    def test_password_hash_roundtrip(self):
        stored = hash_password("Secret123")
        assert verify_password("Secret123", stored)
        assert not verify_password("wrong", stored)
        assert not verify_password("Secret123", "garbage")

    def test_jwt_roundtrip_and_tamper(self):
        jwt = JWT("secret")
        token = jwt.encode({"sub": "alice", "exp": time.time() + 10})
        assert jwt.decode(token)["sub"] == "alice"
        with pytest.raises(AuthError):
            jwt.decode(token[:-3] + "xxx")
        with pytest.raises(AuthError):
            JWT("other-secret").decode(token)

    def test_jwt_expiry(self):
        jwt = JWT("secret")
        token = jwt.encode({"sub": "a", "exp": time.time() - 1})
        with pytest.raises(AuthError) as exc_info:
            jwt.decode(token)
        assert exc_info.value.code == ErrorCode.TOKEN_EXPIRED

    def test_full_auth_flow(self):
        mgr = self._mgr()
        mgr.create_user("alice", "Str0ngPass", role="user")
        tokens = mgr.authenticate("alice", "Str0ngPass")
        payload = mgr.verify_token(tokens["access_token"])
        assert payload["sub"] == "alice"
        assert "chat" in payload["scopes"]
        refreshed = mgr.refresh(tokens["refresh_token"])
        assert mgr.verify_token(refreshed["access_token"])["sub"] == "alice"
        with pytest.raises(AuthError):
            mgr.verify_token(tokens["refresh_token"])  # wrong token type

    def test_lockout_after_failures(self):
        mgr = AuthManager(AuthConfig(enabled=True, jwt_secret="s",
                                     max_failed_attempts=2, lockout_s=60,
                                     min_password_len=8))
        mgr.create_user("bob", "Str0ngPass")
        for _ in range(2):
            with pytest.raises(AuthError):
                mgr.authenticate("bob", "wrong")
        with pytest.raises(AuthError) as exc_info:
            mgr.authenticate("bob", "Str0ngPass")
        assert exc_info.value.code == ErrorCode.ACCOUNT_LOCKED
        mgr._users["bob"].locked_until = 0.0  # simulate lockout expiry
        assert mgr.authenticate("bob", "Str0ngPass")["access_token"]

    def test_password_policy(self):
        mgr = self._mgr()
        for bad in ("short1A", "alllowercase1", "ALLUPPER1", "NoDigitsHere"):
            with pytest.raises(ValueError):
                mgr.create_user(f"u{bad}", bad)

    def test_api_keys(self):
        mgr = self._mgr()
        mgr.create_user("svc", "Str0ngPass", role="service")
        key = mgr.create_api_key("svc")
        payload = mgr.verify_api_key(key)
        assert payload["role"] == "service"
        assert mgr.revoke_api_key(key)
        with pytest.raises(AuthError):
            mgr.verify_api_key(key)

    def test_rbac(self):
        mgr = self._mgr()
        payload = {"role": "user", "scopes": ["read", "chat"]}
        mgr.require_scopes(payload, "read")
        with pytest.raises(ForbiddenError):
            mgr.require_scopes(payload, "admin")
        with pytest.raises(ForbiddenError):
            mgr.require_role(payload, "admin")

    def test_sessions(self):
        mgr = self._mgr()
        s = mgr.create_session("alice")
        assert mgr.get_session(s.session_id).username == "alice"
        assert mgr.end_session(s.session_id)
        assert mgr.get_session(s.session_id) is None


class TestSecurity:
    def test_sanitize_redacts_secrets(self):
        text = 'calling with api_key="sk-12345secret" and Authorization: Bearer abc123'
        out = sanitize_text(text)
        assert "sk-12345secret" not in out
        assert "[REDACTED]" in out

    def test_sanitize_redacts_jwt_and_api_keys(self):
        jwt = JWT("s").encode({"sub": "x"})
        out = sanitize_text(f"token {jwt} key stk_{'a' * 20}")
        assert "[REDACTED_JWT]" in out
        assert "[REDACTED_KEY]" in out

    def test_log_filter(self, caplog):
        logger = logging.getLogger("test_sanitize")
        logger.addFilter(LogSanitizer())
        with caplog.at_level(logging.INFO, logger="test_sanitize"):
            logger.info("password=SuperSecret99")
        assert "SuperSecret99" not in caplog.text

    def test_input_validator_query(self):
        v = InputValidator(max_query_chars=50)
        assert v.validate_query("  what is jax?\x00 ") == "what is jax?"
        with pytest.raises(ValidationError):
            v.validate_query("")
        with pytest.raises(ValidationError):
            v.validate_query("x" * 51)
        with pytest.raises(ValidationError):
            v.validate_query("<script>alert(1)</script>")
        with pytest.raises(ValidationError):
            v.validate_query(42)

    def test_input_validator_metadata(self):
        v = InputValidator()
        assert v.validate_metadata(None) == {}
        assert v.validate_metadata({"k": "v", "n": 3})["n"] == 3
        with pytest.raises(ValidationError):
            v.validate_metadata({"k": ["no", "lists"]})

    def test_rate_limiter_window(self):
        rl = IPRateLimiter()
        rl.configure("/embed", per_minute=2)
        rl.check("1.2.3.4", "/embed")
        rl.check("1.2.3.4", "/embed")
        with pytest.raises(RateLimitError) as exc_info:
            rl.check("1.2.3.4", "/embed")
        assert exc_info.value.details["retry_after_s"] > 0
        rl.check("5.6.7.8", "/embed")  # other IPs unaffected

    def test_rate_limiter_load_factor(self):
        rl = IPRateLimiter()
        rl.configure("/chat", per_minute=10)
        rl.load_factor = 0.1  # under pressure: 1/min
        rl.check("9.9.9.9", "/chat")
        with pytest.raises(RateLimitError):
            rl.check("9.9.9.9", "/chat")

    def test_csrf(self):
        csrf = CSRFProtection()
        token = csrf.issue("sess-1")
        assert csrf.verify("sess-1", token)
        assert not csrf.verify("sess-2", token)
        assert not csrf.verify("sess-1", "junk")


class TestExceptions:
    def test_error_serialization(self):
        err = ValidationError("bad input", details={"field": "question"})
        status, body = ErrorHandler.handle(err)
        assert status == 422
        assert body["error"]["code"] == "VALIDATION_ERROR"
        assert body["error"]["details"]["field"] == "question"

    def test_unknown_exception_opaque(self):
        status, body = ErrorHandler.handle(RuntimeError("secret internals"))
        assert status == 500
        assert "secret internals" not in str(body)

    def test_rate_limit_carries_retry_after(self):
        err = RateLimitError(retry_after_s=12.0)
        assert err.status == 429
        assert err.details["retry_after_s"] == 12.0


class TestMonitoring:
    def test_thresholds_and_trend(self):
        from sentio_tpu.infra.monitoring import PerformanceMonitor

        mon = PerformanceMonitor()
        fired = []
        mon.set_threshold("latency", 100.0)
        mon.on_alert(fired.append)
        for v in (50, 150, 250):
            mon.record("latency", v)
        assert len(fired) == 2
        assert mon.trend("latency")["direction"] == "rising"
        summary = mon.summary("latency")
        assert summary["count"] == 3 and summary["max"] == 250

    def test_health_verdict(self):
        from sentio_tpu.infra.monitoring import ResourceMonitor

        verdict = ResourceMonitor().health_verdict()
        assert verdict["status"] in ("healthy", "degraded", "unhealthy")
        assert "system" in verdict


class TestMetrics:
    def test_record_and_export(self):
        from sentio_tpu.infra.metrics import MetricsCollector

        m = MetricsCollector()
        m.record_request("/chat", 200, 0.12)
        m.record_row_steps({"useful": 3, "halted": 4, "empty": 9},
                           {"held": 21, "tabled": 128})
        snap = m.export_json()
        assert any("requests" in k for k in snap["counters"])
        assert not snap["gauges"]
        assert snap["counters"]["row_steps('empty',)"] == 9.0
        text = m.export_prometheus()
        assert b"sentio_requests_total" in text
        assert b'sentio_tpu_decode_row_steps_total{kind="halted"} 4.0' in text
        assert snap["counters"]["kv_pages('held',)"] == 21.0
        assert b'sentio_tpu_decode_kv_pages_total{kind="tabled"} 128.0' in text
        # series no code wrote are gone, not exported empty
        for dead in (b"sentio_retrieval_latency", b"sentio_circuit_breaker_state",
                     b"sentio_tpu_batch_occupancy", b"sentio_tpu_hbm_bytes_in_use",
                     b"sentio_tpu_decode_tokens_per_second", b"sentio_llm_tokens",
                     b"sentio_llm_latency", b"sentio_embeddings"):
            assert dead not in text

    def test_track_request_context(self):
        from sentio_tpu.infra.metrics import MetricsCollector

        m = MetricsCollector()
        with m.track_request("/info"):
            pass
        with pytest.raises(ValueError):
            with m.track_request("/info"):
                raise ValueError("x")
        snap = m.export_json()
        assert snap["counters"]["requests('/info', '200')"] == 1.0
        assert snap["counters"]["requests('/info', '500')"] == 1.0


def test_csrf_malformed_timestamp_returns_false():
    csrf = CSRFProtection()
    assert csrf.verify("sess", "abc.def") is False
    assert csrf.verify("sess", "..") is False


def test_rate_limiter_sweeps_idle_keys():
    rl = IPRateLimiter()
    rl._checks_since_sweep = 0
    for i in range(100):
        rl.check(f"10.0.0.{i}", "/x")
    # age everything out and force a sweep (the limiter clocks windows on
    # the monotonic perf_counter, not the NTP-steppable epoch clock)
    with rl._lock:
        for key in list(rl._events):
            rl._events[key] = [time.perf_counter() - 120.0]
        rl._checks_since_sweep = 10_000
    rl.check("fresh-ip", "/x")
    assert len(rl._events) <= 2


class TestInflightGauge:
    def test_inflight_tracks_and_floors_at_zero(self):
        from sentio_tpu.infra.metrics import MetricsCollector

        m = MetricsCollector(enabled=True)
        m.adjust_inflight(+1)
        m.adjust_inflight(+1)
        assert m.export_json()["gauges"]["inflight()"] == 2.0
        m.adjust_inflight(-1)
        m.adjust_inflight(-1)
        m.adjust_inflight(-1)  # never below zero
        assert m.export_json()["gauges"]["inflight()"] == 0.0

    def test_track_request_brackets_inflight(self):
        from sentio_tpu.infra.metrics import MetricsCollector

        m = MetricsCollector(enabled=True)
        with m.track_request("/chat"):
            assert m.export_json()["gauges"]["inflight()"] == 1.0
        assert m.export_json()["gauges"]["inflight()"] == 0.0
