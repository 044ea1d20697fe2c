"""Fixtures shared by the engine's tests."""

import pytest

from repro.core import executor, outofcore, spans


@pytest.fixture
def host_copies(monkeypatch):
    """Record what the executor starts early and what the store puts:
    returns ``(started, puts)``, the values handed to
    ``start_host_copy`` and the metadata of each ``ooc.store.put``
    span, in order."""
    started, puts = [], []
    start, span = executor.start_host_copy, outofcore.span

    def counting_start(value):
        started.append(value)
        start(value)

    def recording_span(name, **meta):
        if name == spans.PUT:
            puts.append(meta)
        return span(name, **meta)

    monkeypatch.setattr(executor, "start_host_copy", counting_start)
    monkeypatch.setattr(outofcore, "span", recording_span)
    return started, puts
