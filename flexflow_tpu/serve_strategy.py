"""A paged server's decisions as one value, and the constant its
packing shares with whatever prices it.

A leaf on the serving side: the servers (paged/scheduler.py, serving.py)
and the strategy search (search/servesearch.py, search/ticksim.py) both
import it, neither imports the other for it. Module level imports only
the standard library; `SpecConfig` and `kv_dtype_info` are imported in
the methods that use them (flexflow_tpu/spec/__init__.py imports the
speculative server, which imports the scheduler).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from typing import Dict, Optional, Tuple

# Packed prefill windows are capped at this many rows — the fp32 sublane
# tile. The scheduler launches with it and the tick pricer
# (search/servesearch.py, search/ticksim.py) models the same
# ceil-to-window padding; analysis/shapecheck.py mirrors the integer
# (fflint runs on a bare checkout) and a test pins the two equal.
PREFILL_WINDOW_ROWS = 8


@dataclasses.dataclass(frozen=True)
class ServeStrategy:
    """One point in the serving knob space — everything
    `serve_generation(paged=True)` lets a caller choose, in one
    JSON-serializable value the search walks and the server loads.

    spec_width/spec_depth 0 = speculation off; `mesh` is the serving
    mesh layout as sorted (axis, size) pairs, () = the compiled mesh.
    pool_fraction scales the page pool against the dense capacity
    (slots x pages-per-seq) — the HBM knob; 1.0 keeps the server
    default. kv_dtype picks the pool's storage dtype
    (paged.quant.KV_DTYPES; "auto" = the model's own dtype, "int8" =
    quantized pages with the per-page scale sidecar) — the OTHER HBM
    knob, trading bytes per cached token against a bounded logit
    error instead of trading pages away. host_tier_pages sizes the
    host-RAM KV spill tier (disagg.HostTier) in pages; 0 = no tier
    (LRU evictions drop pages, prefix misses recompute). A tier lets
    the pool trade a PCIe fetch for a prefill recompute — whether
    that wins depends on traffic, which is exactly what the search
    decides."""

    page_size: int = 64
    prefill_chunk: int = 64
    spec_width: int = 0
    spec_depth: int = 0
    pool_fraction: float = 1.0
    kv_dtype: str = "auto"
    host_tier_pages: int = 0
    mesh: Tuple[Tuple[str, int], ...] = ()

    def validate(self, max_len: Optional[int] = None) -> None:
        """Raise ValueError on combinations serve_generation rejects —
        the SAME constraints, so a searched strategy is a servable one."""
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if not (0.0 < self.pool_fraction <= 1.0):
            raise ValueError(
                f"pool_fraction must be in (0, 1], got {self.pool_fraction}")
        if self.host_tier_pages < 0:
            raise ValueError(
                f"host_tier_pages must be >= 0, got {self.host_tier_pages}")
        if (self.spec_width >= 1) != (self.spec_depth >= 1):
            raise ValueError(
                f"spec_width/spec_depth must both be 0 or both >= 1, got "
                f"{self.spec_width}x{self.spec_depth}")
        # typo'd dtypes fail HERE, not as a silently-fp32 served pool
        from flexflow_tpu.paged.quant import kv_dtype_info

        kv_dtype_info(self.kv_dtype)
        if max_len is not None and self.page_size > max_len:
            raise ValueError(
                f"page_size {self.page_size} exceeds max_len {max_len}")

    def spec_config(self):
        """The SpecConfig this strategy stands for, None when
        speculation is off."""
        if self.spec_width < 1:
            return None
        from flexflow_tpu.spec.config import SpecConfig

        return SpecConfig(width=self.spec_width, depth=self.spec_depth)

    def to_server_kwargs(self, slots: int, max_len: int) -> Dict:
        """The serve_generation(...) kwargs this strategy stands for.
        num_pages stays None (the server's dense-capacity default) at
        pool_fraction 1.0; smaller fractions shrink the pool but never
        below one sequence's worth — the pool must admit SOMETHING."""
        self.validate(max_len=max_len)
        pages_per_seq = -(-int(max_len) // self.page_size)
        num_pages = None
        if self.pool_fraction < 1.0:
            num_pages = max(
                int(math.ceil(self.pool_fraction * slots * pages_per_seq)) + 1,
                pages_per_seq + 1)
        return {
            "paged": True,
            "page_size": self.page_size,
            "prefill_chunk": self.prefill_chunk,
            "num_pages": num_pages,
            "speculate": self.spec_config(),
            "kv_dtype": self.kv_dtype,
            "host_tier": self.host_tier_pages or None,
        }

    def describe(self) -> str:
        spec = (f"spec {self.spec_width}x{self.spec_depth}"
                if self.spec_width else "spec off")
        mesh = ",".join(f"{a}={s}" for a, s in self.mesh) or "compiled mesh"
        tier = (f"tier {self.host_tier_pages}p"
                if self.host_tier_pages else "tier off")
        return (f"page {self.page_size} + chunk {self.prefill_chunk} + "
                f"{spec} + pool {self.pool_fraction:g} + "
                f"kv {self.kv_dtype} + {tier} + {mesh}")

    def to_json(self) -> Dict:
        d = dataclasses.asdict(self)
        d["mesh"] = [[a, s] for a, s in self.mesh]
        return d

    @classmethod
    def from_json(cls, d: Dict) -> "ServeStrategy":
        kw = dict(d)
        # stored JSON is outside input, and an older one names the
        # packing: packed is what there is, unpacked cannot be served
        if not kw.pop("ragged_pack", True):
            raise ValueError(
                "stored strategy has \"ragged_pack\": false, a prefill "
                "packing that no longer exists; serve it without the key")
        # ... or a device-resident loop: at its default the key is
        # dropped, set it asked for a loop that is gone
        for key, default in (("megastep_ticks", 1), ("megastep_mixed", False),
                             ("overlap_dispatch", False)):
            if kw.pop(key, default) != default:
                raise ValueError(
                    f"stored strategy has \"{key}\": {json.dumps(d[key])}, "
                    "a device-resident loop that no longer exists; serve it "
                    "without the key")
        kw["mesh"] = tuple((str(a), int(s)) for a, s in kw.get("mesh", ()))
        return cls(**kw)

    def fingerprint(self) -> str:
        """Stable short content hash over the canonical JSON form — the
        strategy's identity across processes. Stamped into every reqlog
        record and the /v2 metrics payload so post-swap records
        attribute to the strategy that actually served them, and equal
        for any two strategies with equal knobs regardless of how they
        were constructed."""
        doc = json.dumps(self.to_json(), sort_keys=True,
                         separators=(",", ":"))
        return hashlib.sha1(doc.encode("utf-8")).hexdigest()[:12]
