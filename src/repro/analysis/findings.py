"""Finding/report datamodel + the rule catalogue for ``repro.analysis``.

Every rule has a stable id (``J*`` jaxpr lints, ``D*`` donation checks,
``K*`` kernel BlockSpec proofs, ``P*`` paging invariants).  DESIGN.md §8
documents each rule, how to add one, and how to silence one
(``--disable RULE`` on the CLI)."""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, List, Optional

RULES: Dict[str, str] = {
    "J001": "stray dequant: int8 -> float convert outside the designated "
            "int32-accumulate epilogue",
    "J002": "unaccumulated dot: int8 dot without int32 output, or "
            "bf16/f16 dot without f32 accumulation",
    "J003": "host transfer: callback / infeed / outfeed / device_put "
            "primitive inside a serving executable",
    "J004": "baked constant: a closed-over array above the size threshold "
            "is burned into the executable (recompile + memory hazard)",
    "J005": "wide dtype leak: float64/complex128 value inside a serving "
            "executable",
    "J006": "logit round trip: model entry returns logits in a dtype "
            "narrower than f32 (sampler upcasts quantized values)",
    "J007": "sharded-surface hazard: compiled SPMD module all-gathers a "
            "full parameter (sharding constraint undone downstream) or "
            "moves data device-to-host mid-executable",
    "D001": "dead donation: donated input buffer matches no output buffer "
            "(donation silently dropped)",
    "D002": "duplicate donation: more donated buffers of a (shape, dtype) "
            "than outputs that can absorb them",
    "K001": "out-of-bounds block: a BlockSpec index map can return a block "
            "index (or read a scalar table entry) outside its domain",
    "K002": "dead block not elided: DMA count along the innermost grid axis "
            "exceeds the pl.when-live block count (dead blocks must remap "
            "to a live index so the revisit DMA is elided)",
    "K003": "output revisit: output index map varies along a reduction grid "
            "axis (partial accumulator stores)",
    "K004": "untileable block: one of a block's last two dims is neither a "
            "multiple of the TPU tile (8 sublanes, 128 lanes) nor the whole "
            "array dim (Mosaic refuses to lower the kernel)",
    "P001": "paging invariant violation (PagePool/RadixCache structural "
            "check, see paging.check_invariants)",
    "R001": "unreachable resilience branch: a FinishReason the Scheduler "
            "must be able to emit was not produced by the canonical "
            "degraded-mode scenario suite (see runner.check_resilience)",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    message: str
    context: str = ""          # e.g. "config=olmo-1b mode=interpret entry=decode"
    file: Optional[str] = None
    line: Optional[int] = None
    severity: str = "error"

    def where(self) -> str:
        if self.file:
            return f"{self.file}:{self.line or 0}"
        return "<no provenance>"

    def __str__(self) -> str:
        ctx = f" [{self.context}]" if self.context else ""
        return f"{self.rule} {self.where()}{ctx}: {self.message}"

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Report:
    findings: List[Finding] = dataclasses.field(default_factory=list)
    checked: List[str] = dataclasses.field(default_factory=list)
    disabled: List[str] = dataclasses.field(default_factory=list)

    def add(self, finding: Finding) -> None:
        if finding.rule not in self.disabled:
            self.findings.append(finding)

    def extend(self, findings) -> None:
        for f in findings:
            self.add(f)

    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == "error"]

    def exit_code(self, strict: bool = False) -> int:
        if strict:
            return 1 if self.findings else 0
        return 1 if self.errors() else 0

    def to_json(self) -> Dict[str, Any]:
        return {
            "findings": [f.to_json() for f in self.findings],
            "checked": self.checked,
            "disabled": self.disabled,
            "rules": RULES,
        }

    def dump(self, path: str) -> None:
        with open(path, "w") as fh:
            json.dump(self.to_json(), fh, indent=2, sort_keys=True)
            fh.write("\n")
