"""Serving shell: the dedup query service over a warm ``DedupSession``."""
from repro.serving.dedup_service import (
    DedupQueryService,
    QueryRequest,
    QueryServiceStats,
)

__all__ = ["DedupQueryService", "QueryRequest", "QueryServiceStats"]
