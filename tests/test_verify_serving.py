"""ISSUE 11 tier-1 acceptance: confidence-gated async verification through
the REAL serve stack (tiny paged TPU engine on CPU).

Lives outside test_serve.py on purpose: that module is slow-marked, and the
acceptance criteria — zero verify-decode admissions for a confident request,
first token before the trailing verify verdict for a low-confidence stream —
must gate tier-1."""

from sentio_tpu.config import GeneratorConfig
from test_serve import fast_settings, run, seed, with_client


class TestConfidenceGatedVerify:
    """ISSUE 11 acceptance: with VERIFY_MODE=gated, a confident request
    completes with ZERO verify-decode admissions (flight + WFQ counters),
    and a low-confidence streamed request delivers its first token before
    the verify verdict while the trailing ``verify`` SSE event still
    arrives after [DONE]."""

    @staticmethod
    def _paged_settings(threshold: float):
        return fast_settings(
            generator=GeneratorConfig(
                provider="tpu", model_preset="tiny", use_verifier=True,
                verify_mode="gated", verify_confidence_threshold=threshold,
                max_new_tokens=8, verifier_max_tokens=4, mode="fast",
                kv_page_size=16,
                kv_max_pages_per_seq=8, max_batch_size=4,
            ),
        )

    def test_confident_request_skips_verify_with_zero_admissions(self):
        # threshold 0.0: any scored confidence clears the gate, so the
        # skip path is deterministic — the assertion is that NO verify
        # decode ever reaches the engine or the fair queue
        settings = self._paged_settings(threshold=0.0)

        async def body(client, container):
            await seed(client, ["paged decode gating document"])
            resp = await client.post("/chat", json={
                "question": "what about gating?", "thread_id": "gatedskip1",
            })
            assert resp.status == 200, await resp.text()
            data = await resp.json()
            evaluation = data["metadata"].get("evaluation")
            assert evaluation and evaluation["verdict"] == "skipped_confident", data
            assert evaluation["confidence"] >= 0.0
            assert "verify_pending" not in data["metadata"]

            # flight counters: exactly ONE engine admission (the generate
            # decode) — the verify node never admitted
            flight = await (await client.get("/debug/flight/gatedskip1")).json()
            assert len(flight["engine"]["admissions"]) == 1, flight["engine"]
            assert flight["verify"]["outcome"] == "skipped_confident"
            assert flight["verify"]["mode"] == "gated"

            # WFQ counters: one admission charged to the shared tenant —
            # a verify decode would have charged a second
            service = container.generation_service
            if hasattr(service, "tenants"):
                per = service.tenants.stats()["per_tenant"]
                assert sum(t["admitted"] for t in per.values()) == 1, per

            # the gate's outcome is a first-class metric
            prom = await (await client.get("/metrics")).text()
            assert ('sentio_tpu_verify_total{mode="gated",'
                    'outcome="skipped_confident"}') in prom

        run(with_client(settings, body))

    def test_low_confidence_stream_gets_trailing_verify_event(self):
        # threshold > 1.0 is unreachable: every request takes the async
        # path — answer tokens and [DONE] first, the audit verdict as a
        # trailing `verify` event on the still-open connection
        settings = self._paged_settings(threshold=1.1)

        async def body(client, container):
            await seed(client, ["trailing verdict streaming document"])
            resp = await client.post("/chat", json={
                "question": "what about trailing verdicts?", "stream": True,
            })
            assert resp.status == 200
            import json as _json

            events = []
            for line in (await resp.read()).decode().splitlines():
                if line.startswith("data:"):
                    data = line[5:].strip()
                    if data == "[DONE]":
                        events.append(("done", None))
                    else:
                        events.append(next(iter(_json.loads(data).items())))
            kinds = [k for k, _ in events]
            assert "token" in kinds and "done" in kinds, kinds
            assert "verify" in kinds, (
                f"trailing verify event missing: {kinds}")
            first_token = kinds.index("token")
            done_at = kinds.index("done")
            verify_at = kinds.index("verify")
            # first token precedes the verdict; the verdict trails [DONE]
            assert first_token < done_at < verify_at, kinds
            verdict = dict(events[verify_at][1])
            assert verdict["verdict"] in ("pass", "warn", "fail")
            # the gate scored the answer (paged logprobs flowed) but it
            # stayed below the unreachable threshold
            assert verdict.get("confidence") is not None
            assert verdict["confidence"] < 1.1

        run(with_client(settings, body))
