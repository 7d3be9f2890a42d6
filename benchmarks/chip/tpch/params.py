"""The substitution values of the six queries (TPC-H clause 2.4, the
validation values), shared by the query builders and the references.
Nothing here imports the program."""

from __future__ import annotations

from typing import Tuple

from .datagen import CONTAINERS, TYPES, code, day

Q1_CUTOFF = day(1998, 12, 1) - 90
#: p_type codes of the "PROMO%" types, first and last (Q14)
PROMO_TYPES = (min(i for i, t in enumerate(TYPES) if t.startswith("PROMO")),
               max(i for i, t in enumerate(TYPES) if t.startswith("PROMO")))
Q19_CONTAINERS = {
    "SM": ("SM CASE", "SM BOX", "SM PACK", "SM PKG"),
    "MED": ("MED BAG", "MED BOX", "MED PKG", "MED PACK"),
    "LG": ("LG CASE", "LG BOX", "LG PACK", "LG PKG"),
}
#: (brand, container group, quantity range, largest size) of Q19's three arms
Q19_ARMS = (("Brand#12", "SM", (1.0, 11.0), 5),
            ("Brand#23", "MED", (10.0, 20.0), 10),
            ("Brand#34", "LG", (20.0, 30.0), 15))


def containers(group: str) -> Tuple[int, ...]:
    return tuple(code(CONTAINERS, c) for c in Q19_CONTAINERS[group])
