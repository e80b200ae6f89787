"""A configuration's query inputs, made from the seed on the host.

The engine has no expression stage, so the filter and the projections a
query needs are applied here, in NumPy, as set-up: the engine is given
key columns and float32 value columns only.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from chipbench.data import tpch


def _disc_price(c):
    return c["extendedprice_cents"] * (100 - c["discount_pct"]) / 10_000


def _charge(c):
    return (c["extendedprice_cents"] * (100 - c["discount_pct"])
            * (100 + c["tax_pct"]) / 1_000_000)


DERIVED = {"disc_price": _disc_price, "charge": _charge}


@dataclasses.dataclass
class Query:
    config: dict
    keys: dict[str, np.ndarray]        # uint32 key columns, major first
    values: np.ndarray | None          # (N, V) float32
    bits: dict[str, int]               # KeySpec widths

    @property
    def rows(self) -> int:
        return len(next(iter(self.keys.values())))

    @property
    def key_cols(self) -> list[np.ndarray]:
        return list(self.keys.values())

    def input_row_bytes(self) -> int:
        v = 0 if self.values is None else self.values.shape[1]
        return 4 * ((sum(self.bits.values()) + 31) // 32) + 4 * v

    def output_row_bytes(self) -> int:
        v = 0 if self.values is None else self.values.shape[1]
        sums = v if "sum" in self.config["aggs"] or "avg" in self.config["aggs"] else 0
        return 4 * ((sum(self.bits.values()) + 31) // 32) + 4 + 4 * sums


def build(config: dict, seed: int) -> Query:
    cols = tpch.lineitem(config["scale_factor"], seed,
                         block_rows=config["exec_config"]["batch_rows"],
                         shards=config["chips"])
    where = config.get("where")
    if where:
        keep = np.ones(len(cols["l_orderkey"]), bool)
        for col, iso in where.get("le", {}).items():
            keep &= cols[col] <= tpch.day(iso)
        cols = {k: v[keep] for k, v in cols.items()}
    keys = {k: cols[k] for k in config["group_by"]}
    values = None
    if config.get("values"):
        values = np.stack(
            [(DERIVED[v](cols) if v in DERIVED else cols[v]).astype(np.float32)
             for v in config["values"]], axis=1)
    bits = {k: max(1, int(v.max()).bit_length()) for k, v in keys.items()}
    return Query(config=config, keys=keys, values=values, bits=bits)
