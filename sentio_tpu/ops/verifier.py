"""AnswerVerifier: LLM self-audit of generated answers.

Parity with /root/reference/src/core/llm/answer_verifier.py:20-88: a
temperature-0, bounded-token audit call that returns a normalized
``{verdict: pass|warn|fail, citations_ok, notes[<=8], revised_answer?}``
verdict, NEVER raises (conservative ``warn`` on any failure), and shares the
generator's weights — on TPU the audit is just another forward pass on the
same sharded params, not a second remote model.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

from sentio_tpu.config import GeneratorConfig, get_settings
from sentio_tpu.infra.tracing import span
from sentio_tpu.models.document import Document
from sentio_tpu.ops.generator import LLMGenerator
from sentio_tpu.ops.prompts import PromptBuilder
from sentio_tpu.ops.reply_extractor import extract_json_block

VALID_VERDICTS = ("pass", "warn", "fail")


@dataclass
class VerifyResult:
    verdict: str = "warn"
    citations_ok: bool = True
    notes: list[str] = field(default_factory=list)
    revised_answer: Optional[str] = None

    def to_dict(self) -> dict:
        out = {
            "verdict": self.verdict,
            "citations_ok": self.citations_ok,
            "notes": self.notes,
        }
        if self.revised_answer:
            out["revised_answer"] = self.revised_answer
        return out


@dataclass
class AnswerVerifier:
    generator: LLMGenerator
    config: GeneratorConfig = field(default_factory=lambda: get_settings().generator)
    prompts: PromptBuilder = field(default_factory=PromptBuilder)

    def verify(
        self,
        query: str,
        answer: str,
        documents: Sequence[Document],
        request_id: Optional[str] = None,
        deadline_ts: Optional[float] = None,
        tenant: Optional[str] = None,
        priority: Optional[str] = None,
    ) -> VerifyResult:
        try:
            # the `verify` request stage: the audit's own admission writes
            # its inbox_wait/slot_wait/prefill/decode under this span
            with span("verify", request_id=request_id):
                # the audit prompt EMBEDS the generate prompt verbatim as its
                # head (same instruction profile + context + question, in the
                # same bytes) — on the paged engine the radix prefix cache then
                # serves that whole span from the generate admission's KV pages
                # and this call prefills only the audit tail
                context = self.generator.prepare_context(documents)
                prompt = self.prompts.build(
                    "verify",
                    instruction=self.prompts.load("profile"),
                    context=context,
                    query=query,
                    answer=answer,
                )
                # the caller's deadline bounds the audit decode too — an
                # expired caller's verification is cancelled like its
                # generation — and the audit admission is charged to the
                # caller's WFQ tenant (a flooding tenant's verify traffic
                # competes inside ITS quota, not against everyone)
                reply = self.generator.chat_raw(
                    prompt,
                    max_new_tokens=self.config.verifier_max_tokens,
                    temperature=0.0,
                    request_id=request_id,
                    deadline_ts=deadline_ts,
                    tenant=tenant,
                    priority=priority,
                )
            return self._normalize(reply)
        except Exception as exc:  # noqa: BLE001 — the audit must never 500
            return VerifyResult(verdict="warn", notes=[f"verifier error: {exc}"])

    def _normalize(self, reply: str) -> VerifyResult:
        extracted = extract_json_block(reply)
        if not extracted.ok:
            return VerifyResult(verdict="warn", notes=[f"unparseable audit: {extracted.error}"])
        data = extracted.payload
        verdict = str(data.get("verdict", "warn")).lower()
        if verdict not in VALID_VERDICTS:
            verdict = "warn"
        notes_raw = data.get("notes", [])
        if isinstance(notes_raw, str):
            notes_raw = [notes_raw]
        notes = [str(n) for n in notes_raw][:8]
        revised = data.get("revised_answer")
        return VerifyResult(
            verdict=verdict,
            citations_ok=bool(data.get("citations_ok", True)),
            notes=notes,
            revised_answer=str(revised) if revised else None,
        )
