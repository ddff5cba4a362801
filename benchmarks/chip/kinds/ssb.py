"""The Star Schema Benchmark's query flight, Q1.1 to Q4.3 (O'Neil et
al., TPCTC 2009), as WHERE clauses: an AND of clauses, each an OR of
``attribute = value`` terms.

Mix entry: ``{"kind": "ssb", "share": 1}``.  Each query is one of the 13
templates, drawn uniformly.  A template keeps its published clauses and
the published count of values in each (``WIDTHS``): Q2.2's 8 consecutive
brands of one category, Q3.1's six consecutive years; its constants are
drawn from the stream, so each keeps its published filter factor.  A
query is ``{"kind", "template", "clauses"}``, each clause a list of
``[attribute, value]``; the program gets ``Query.and_of_or`` over the
bitmaps named ``attribute=value`` (``configs/ssb_sf1.py``).

The reference evaluates the WHERE clause over the generated tables -- a
predicate on each dimension table, joined to the lineorder rows by key --
and not over the bitmaps; a served ``RoaringBitmap`` is right when its
sorted values equal the reference's row ids.  The control is what a
planner that lost a clause would serve: the reference's answer to the
query without its last clause.
"""

from __future__ import annotations

import numpy as np

WIDTHS = {
    "Q1.1": (1, 3, 24), "Q1.2": (1, 3, 10), "Q1.3": (1, 1, 3, 10),
    "Q2.1": (1, 1), "Q2.2": (8, 1), "Q2.3": (1, 1),
    "Q3.1": (1, 1, 6), "Q3.2": (1, 1, 6), "Q3.3": (2, 2, 6),
    "Q3.4": (2, 2, 1),
    "Q4.1": (1, 1, 2), "Q4.2": (1, 1, 2, 2), "Q4.3": (1, 1, 2, 1)}
TEMPLATES = sorted(WIDTHS)
FULL_YEARS = range(1992, 1998)      # the years whose every day has orders


def _pick(rng, seq, n=1, consecutive=False):
    """``n`` items of ``seq``: distinct ones, or a consecutive run."""
    seq = list(seq)
    if consecutive:
        i = int(rng.integers(0, len(seq) - n + 1))
        return seq[i:i + n]
    return [seq[i] for i in sorted(rng.choice(len(seq), n, replace=False))]


def _clause(attr, values):
    return [[attr, str(v)] for v in values]


def draw(template: str, data, rng) -> list:
    """The clauses of one query of ``template`` over ``data``'s values."""
    v = data.values
    cpn = data.cities_per_nation
    region = int(rng.integers(0, len(v["c_region"])))
    nations = np.flatnonzero(data.nation_region == region)
    nation = int(rng.choice(nations))
    year = _pick(rng, FULL_YEARS)
    month = [f"{year[0]}{int(rng.integers(1, 13)):02d}"]
    disc = lambda w: _pick(rng, v["lo_discount"], w, True)
    qty = lambda w: _pick(rng, v["lo_quantity"], w, True)
    cities = [v["c_city"][nation * cpn + d]
              for d in _pick(rng, range(cpn), 2)]
    reg = [v["c_region"][region]]
    nat = [v["c_nation"][nation]]
    category = int(rng.integers(0, len(v["p_category"])))
    bpc = data.brands_per_category
    brands = v["p_brand1"][category * bpc:(category + 1) * bpc]
    two_years = _pick(rng, range(1992, 1999), 2, True)
    mfgrs = _pick(rng, v["p_mfgr"], 2)
    return {
        "Q1.1": lambda: [_clause("d_year", year), _clause(
            "lo_discount", disc(3)), _clause("lo_quantity", qty(24))],
        "Q1.2": lambda: [_clause("d_yearmonthnum", month), _clause(
            "lo_discount", disc(3)), _clause("lo_quantity", qty(10))],
        "Q1.3": lambda: [_clause("d_weeknuminyear", [int(rng.integers(
            1, 53))]), _clause("d_year", year), _clause(
            "lo_discount", disc(3)), _clause("lo_quantity", qty(10))],
        "Q2.1": lambda: [_clause("p_category", [v["p_category"][category]]),
                         _clause("s_region", reg)],
        "Q2.2": lambda: [_clause("p_brand1", _pick(rng, brands, 8, True)),
                         _clause("s_region", reg)],
        "Q2.3": lambda: [_clause("p_brand1", _pick(rng, brands)),
                         _clause("s_region", reg)],
        "Q3.1": lambda: [_clause("c_region", reg), _clause("s_region", reg),
                         _clause("d_year", _pick(rng, range(1992, 1999), 6,
                                                 True))],
        "Q3.2": lambda: [_clause("c_nation", nat), _clause("s_nation", nat),
                         _clause("d_year", _pick(rng, range(1992, 1999), 6,
                                                 True))],
        "Q3.3": lambda: [_clause("c_city", cities), _clause("s_city", cities),
                         _clause("d_year", _pick(rng, range(1992, 1999), 6,
                                                 True))],
        "Q3.4": lambda: [_clause("c_city", cities), _clause("s_city", cities),
                         _clause("d_yearmonthnum", month)],
        "Q4.1": lambda: [_clause("c_region", reg), _clause("s_region", reg),
                         _clause("p_mfgr", mfgrs)],
        "Q4.2": lambda: [_clause("c_region", reg), _clause("s_region", reg),
                         _clause("d_year", two_years),
                         _clause("p_mfgr", mfgrs)],
        "Q4.3": lambda: [_clause("c_region", reg),
                         _clause("s_nation", [v["s_nation"][int(
                             rng.choice(nations))]]),
                         _clause("d_year", two_years),
                         _clause("p_category", [v["p_category"][category]])],
    }[template]()


def queries(entry: dict, data, rng):
    while True:
        template = TEMPLATES[int(rng.integers(len(TEMPLATES)))]
        yield {"kind": "ssb", "template": template,
               "clauses": draw(template, data, rng)}


def to_query(q: dict):
    from repro.serve import Query
    return Query.and_of_or(*[[f"{attr}={value}" for attr, value in clause]
                             for clause in q["clauses"]])


class Reference:
    """Row masks of ``attribute = value`` predicates over the tables:
    each dimension predicate is evaluated on its own table and joined to
    the lineorder rows by key."""

    def __init__(self, data):
        self.data = data
        lo = data.lineorder
        self.keys = {"d": lo["orderdate"].astype(np.int32),
                     "c": lo["custkey"] - 1, "s": lo["suppkey"] - 1,
                     "p": lo["partkey"] - 1}

    def _dimension(self, attr: str, values: list) -> np.ndarray:
        """The predicate over the rows of ``attr``'s own table."""
        d = self.data
        table, col = attr.split("_", 1)
        if table == "d":
            return np.isin(d.date[col], [int(x) for x in values])
        if table == "p":
            codes = [d.values[attr].index(x) for x in values]
            return np.isin(d.part[col], codes)
        place = d.customer if table == "c" else d.supplier
        codes = [d.values[attr].index(x) for x in values]
        if col == "region":
            return np.isin(d.nation_region[place["nation"]], codes)
        return np.isin(place[col], codes)

    def rows(self, clauses: list) -> np.ndarray:
        """The sorted row ids that satisfy every clause; each clause is
        evaluated only at the rows that passed the ones before it."""
        rows = np.arange(self.data.universe)
        for clause in clauses:
            attr = clause[0][0]
            values = [value for _, value in clause]
            table = attr.split("_", 1)[0]
            if table == "lo":
                col = self.data.lineorder[attr[3:]][rows]
                rows = rows[np.isin(col, [int(x) for x in values])]
            else:
                rows = rows[self._dimension(attr, values)[
                    self.keys[table][rows]]]
        return rows


def reference(data) -> Reference:
    return Reference(data)


def expected(ref: Reference, q: dict) -> np.ndarray:
    return ref.rows(q["clauses"])


def control(ref: Reference, q: dict) -> np.ndarray:
    return ref.rows(q["clauses"][:-1])


def same(got, want) -> bool:
    """``got``: a served ``RoaringBitmap``, or the control's row ids."""
    if hasattr(got, "to_array"):
        got = got.to_array()
    return np.array_equal(np.asarray(got, np.int64), want)
