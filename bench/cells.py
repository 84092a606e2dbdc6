"""Find a cell by its name in ``BENCHMARK.json``, with the files it names.

A cell is one entry of ``workloads``: a configuration (``configs``'s
``file``) under a traffic mix (``bench/traffic/<traffic>.json``).  Nothing
here knows a cell by name: a later cell is new entries and new files.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import List, Optional

REPO = pathlib.Path(__file__).resolve().parent.parent
TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict                    # the configuration file
    traffic: dict                 # the traffic file
    end_to_end: List[dict]        # metrics this cell reports with --trace 0
    per_layer: List[dict]         # ... and with --trace 1

    @property
    def n_slots(self) -> int:
        return int(self.spec["serving"]["n_slots"])

    @property
    def max_prompt(self) -> int:
        return max(self.traffic["prompt_tokens"]["values"])

    @property
    def max_output(self) -> int:
        return max(self.traffic["output_tokens"]["values"])

    def engine_config(self):
        """The program's ``EngineConfig``: paged plane, no prefix sharing,
        a slot as long as the longest request."""
        from repro.serve.engine import EngineConfig
        serving = self.spec["serving"]
        return EngineConfig(
            n_slots=self.n_slots, max_prompt_len=self.max_prompt,
            max_new_cap=self.max_output,
            cache_len=self.max_prompt + self.max_output,
            max_queue=1 << 16,
            max_prefill_per_step=int(serving["max_prefill_per_step"]),
            page_size=int(serving["page_size"]), prefix_sharing=False)


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Optional[pathlib.Path] = None) -> Cell:
    root = pathlib.Path(root or REPO)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    w = found[0]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    spec = json.loads((root / conf["file"]).read_text())
    traffic_dir = root / TRAFFIC_DIR.relative_to(REPO)
    traffic = json.loads((traffic_dir / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), spec=spec, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)
