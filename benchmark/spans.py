"""The benchmark's own span sink, handed to the service as `recorder=` in the
traced run only. It takes what `BatchVerifierService` emits (`span`,
`instant`, `flow`, `name_thread`, the `enabled` flag) and keeps complete
spans as (name, start_s, end_s, tid, args) on the host's epoch clock
(`time.time`, the clock the service stamps them with)."""

from __future__ import annotations


class SpanSink:
    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.spans: list[tuple] = []
        self.instants: list[tuple] = []
        self.threads: dict[int, str] = {}

    def span(self, name, start, end, tid=0, cat="", args=None) -> None:
        if self.enabled:
            self.spans.append((name, start, end, tid, args or {}))

    def instant(self, name, ts=None, tid=0, cat="", args=None) -> None:
        if self.enabled:
            self.instants.append((name, ts, tid, args or {}))

    def flow(self, *a, **kw) -> None:
        pass

    def name_thread(self, tid: int, name: str) -> None:
        self.threads[tid] = name

    def named(self, name: str, t0: float = 0.0, t1: float = float("inf")):
        """Spans of one name that END inside [t0, t1]."""
        return [s for s in self.spans if s[0] == name and t0 <= s[2] <= t1]
