"""Data of ``ssb_sf1``: the Star Schema Benchmark's tables at a scale
factor (O'Neil, O'Neil, Chen, Revilak, TPCTC 2009), drawn by dbgen's
rules, and the program's index over them: one bitmap over lineorder rows
per value of each dimension attribute the 13 queries filter on.

The sizes are the configuration's: ``orders`` orders of 1 to 7 lines,
``customers``, ``suppliers``, ``parts`` and the 2,556 days of 1992-1998.
The laws the specification leaves to dbgen are under ``assumed``.  Every
column is drawn in bulk from ``data_seed``; a run's seed changes only the
queries (``kinds/ssb.py``).  Lineorder row ``r`` is bit ``r`` of every
bitmap.

``generate`` first checks that the program serves the ``and_of_or``
query kind, and raises at once where it does not, before any table is
built.
"""

from __future__ import annotations

import dataclasses

import numpy as np

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# TPC-H's NATION table: name and region key, by nation key
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1)]
MFGRS = 5
CATEGORIES_PER_MFGR = 5


def city_name(nation: int, digit: int) -> str:
    return f"{NATIONS[nation][0][:9]:<9}{digit}"


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), stream])


@dataclasses.dataclass
class StarSchema:
    """The drawn tables.  ``lineorder`` holds one array per column, one
    entry per fact row: ``orderdate`` (a day index into ``date``),
    ``custkey``, ``partkey``, ``suppkey`` (1-based keys), ``quantity``
    and ``discount``.  ``customer`` and ``supplier`` hold ``nation`` and
    ``city`` codes by key - 1 (city = nation * cities_per_nation +
    digit); ``part`` holds ``mfgr``, ``category`` and ``brand1`` codes
    (0-based, each within its parent); ``date`` holds ``year``,
    ``yearmonthnum`` and ``weeknuminyear`` by day index;
    ``nation_region`` the region code of each nation.  ``values``
    names each indexed attribute's values, by code; ``names`` and
    ``counts`` are the program's bitmaps (``attribute=value``, those
    with rows) and their cardinalities."""
    lineorder: dict
    customer: dict
    supplier: dict
    part: dict
    date: dict
    values: dict
    nation_region: np.ndarray
    cities_per_nation: int
    brands_per_category: int
    names: list = dataclasses.field(default_factory=list)
    counts: np.ndarray = None

    @property
    def universe(self) -> int:
        return int(self.lineorder["custkey"].size)

    def __len__(self) -> int:
        return len(self.names)

    def sizes(self) -> np.ndarray:
        return self.counts


def require_and_of_or() -> None:
    """Raise unless the program serves the ``and_of_or`` query kind."""
    from repro.serve import query_server
    if "and_of_or" not in getattr(query_server, "BOOLEAN_KINDS", ()):
        raise RuntimeError("ssb_sf1 needs the program's and_of_or query "
                           "kind, which this program does not serve")


def _dates(cfg: dict) -> dict:
    days = np.arange(np.datetime64(cfg["first_date"]),
                     np.datetime64(cfg["last_date"]) + 1)
    year = days.astype("datetime64[Y]").astype(np.int64) + 1970
    month = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
    doy = (days - days.astype("datetime64[Y]")).astype(np.int64) + 1
    return {"day": days, "year": year, "yearmonthnum": year * 100 + month,
            "weeknuminyear": (doy - 1) // 7 + 1}


def _places(n: int, per: int, rng) -> dict:
    nation = rng.integers(0, len(NATIONS), n)
    return {"nation": nation,
            "city": nation * per + rng.integers(0, per, n)}


def _lineorder(cfg: dict, date: dict, rng) -> dict:
    lo, hi = cfg["lines_per_order"]
    lines = rng.integers(lo, hi + 1, int(cfg["orders"]))
    last = np.datetime64(cfg["last_date"]) - cfg["order_date_lag_days"]
    n_days = int(np.searchsorted(date["day"], last, side="right"))
    # custkey: the k-th key not divisible by 3 is k + k // 2 + 1
    k = rng.integers(0, int(cfg["customers"]) * 2 // 3, lines.size)
    cust = k + k // 2 + 1
    order_date = rng.integers(0, n_days, lines.size)
    n = int(lines.sum())
    s, p = int(cfg["suppliers"]), int(cfg["parts"])
    part = rng.integers(1, p + 1, n)
    i = rng.integers(0, 4, n)
    supp = (part + i * (s // 4 + (part - 1) // s)) % s + 1
    q0, q1 = cfg["quantity"]
    d0, d1 = cfg["discount"]
    return {"orderdate": np.repeat(order_date, lines).astype(np.int16),
            "custkey": np.repeat(cust, lines).astype(np.int32),
            "partkey": part.astype(np.int32),
            "suppkey": supp.astype(np.int32),
            "quantity": rng.integers(q0, q1 + 1, n).astype(np.int8),
            "discount": rng.integers(d0, d1 + 1, n).astype(np.int8)}


def generate(cfg: dict, rng: np.random.Generator) -> StarSchema:
    """The tables of ``data_seed`` (``rng``, the run's, is not used: the
    run's seed changes the queries, not the data)."""
    require_and_of_or()
    seed = cfg["data_seed"]
    cpn, bpc = int(cfg["cities_per_nation"]), int(cfg["brands_per_category"])
    date = _dates(cfg)
    mfgr = _rng(seed, 3).integers(0, MFGRS, int(cfg["parts"]))
    cat = mfgr * CATEGORIES_PER_MFGR + _rng(seed, 4).integers(
        0, CATEGORIES_PER_MFGR, mfgr.size)
    part = {"mfgr": mfgr, "category": cat,
            "brand1": cat * bpc + _rng(seed, 5).integers(0, bpc, mfgr.size)}
    years = np.unique(date["year"])
    months = np.unique(date["yearmonthnum"])
    q0, q1 = cfg["quantity"]
    d0, d1 = cfg["discount"]
    mn = [f"{m + 1}{n + 1}" for m in range(MFGRS)
          for n in range(CATEGORIES_PER_MFGR)]
    place = {"region": REGIONS, "nation": [n for n, _ in NATIONS],
             "city": [city_name(n, d) for n in range(len(NATIONS))
                      for d in range(cpn)]}
    values = {"d_year": [str(y) for y in years],
              "d_yearmonthnum": [str(m) for m in months],
              "d_weeknuminyear": [str(w) for w in range(1, 54)],
              "lo_discount": [str(d) for d in range(d0, d1 + 1)],
              "lo_quantity": [str(q) for q in range(q0, q1 + 1)],
              "p_mfgr": [f"MFGR#{m + 1}" for m in range(MFGRS)],
              "p_category": [f"MFGR#{x}" for x in mn],
              "p_brand1": [f"MFGR#{x}{b + 1}" for x in mn
                           for b in range(bpc)]}
    for side in "cs":
        for attr, names in place.items():
            values[f"{side}_{attr}"] = names
    return StarSchema(lineorder=_lineorder(cfg, date, _rng(seed, 0)),
                      customer=_places(int(cfg["customers"]), cpn,
                                       _rng(seed, 1)),
                      supplier=_places(int(cfg["suppliers"]), cpn,
                                       _rng(seed, 2)),
                      part=part, date=date, values=values,
                      nation_region=np.array([r for _, r in NATIONS]),
                      cities_per_nation=cpn, brands_per_category=bpc)


def fact_codes(data: StarSchema) -> dict:
    """Each indexed attribute's value code for every fact row: the
    lineorder columns joined with their dimensions by key."""
    lo, d = data.lineorder, data.date
    day = lo["orderdate"].astype(np.int64)
    year0 = int(d["year"][0])
    ym = d["yearmonthnum"][day]
    out = {"d_year": d["year"][day] - year0,
           "d_yearmonthnum": (ym // 100 - year0) * 12 + ym % 100 - 1,
           "d_weeknuminyear": d["weeknuminyear"][day] - 1,
           "lo_discount": lo["discount"].astype(np.int64)
           - int(data.values["lo_discount"][0]),
           "lo_quantity": lo["quantity"].astype(np.int64)
           - int(data.values["lo_quantity"][0])}
    for side, table, key in (("c", data.customer, "custkey"),
                             ("s", data.supplier, "suppkey")):
        row = lo[key].astype(np.int64) - 1
        nation = table["nation"][row]
        out[f"{side}_region"] = data.nation_region[nation]
        out[f"{side}_nation"] = nation
        out[f"{side}_city"] = table["city"][row]
    row = lo["partkey"].astype(np.int64) - 1
    for attr in ("mfgr", "category", "brand1"):
        out[f"p_{attr}"] = data.part[attr][row]
    return out


def _bitmap(rows: np.ndarray):
    """A RoaringBitmap of sorted distinct row ids, one container a chunk."""
    from repro.core import RoaringBitmap
    from repro.core.containers import container_from_values
    keys, starts = np.unique(rows >> 16, return_index=True)
    bounds = np.append(starts, rows.size)
    return RoaringBitmap(
        keys.tolist(),
        [container_from_values((rows[a:b] & 0xFFFF).astype(np.uint16))
         for a, b in zip(bounds[:-1], bounds[1:])])


def postings(data: StarSchema) -> dict:
    """The program's bitmaps, ``attribute=value`` for every value that
    has rows; records their names and cardinalities on ``data``."""
    out = {}
    for attr, codes in fact_codes(data).items():
        names = data.values[attr]
        order = np.argsort(codes, kind="stable").astype(np.int64)
        bounds = np.concatenate(
            ([0], np.cumsum(np.bincount(codes, minlength=len(names)))))
        for code, name in enumerate(names):
            a, b = bounds[code], bounds[code + 1]
            if b > a:
                out[f"{attr}={name}"] = _bitmap(order[a:b])
    data.names = list(out)
    data.counts = np.array([bm.cardinality for bm in out.values()],
                           np.int64)
    return out
