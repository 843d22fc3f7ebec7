"""A configuration's tables, made on the device from a seed.

`generate(config, seed)` builds every table of a configuration file
(`bench/configs/<name>.json`) in one jitted call, so the same seed gives
the same columns on any backend (JAX's counter-based PRNG). Each table has
one int32 key column and its attributes:

  key, kind "primary"   the values 0..rows-1, shuffled (the paper's
                        section 5.1)
  key, kind "foreign"   uniform over the referenced table's rows
                        ("references": <table>), or over 0..n-1
                        ("domain": n), a domain no table holds: two
                        tables that both draw from one domain share its
                        values, and a join on them is m:n
  attribute             uniform integers in [low, high], times
                        "multiplier"; an 8-byte attribute is stored as two
                        int32 words, <name>_hi (the sign) and <name>_lo

A key column's multiset of values is drawn from `KEY_SEED`, the same for
every seed; the seed shuffles it into row order and draws the attributes.
The planner sizes its buffers from statistics of the keys (a
distinct-count sketch), so keys drawn anew per seed would compile a new
program for every seed inside set-up.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

KEY_SEED = 0


def column_names(table: dict) -> list[str]:
    """The int32 columns a table spec is stored as, key first."""
    names = [table["key"]["name"]]
    for attr in table["attributes"]:
        if attr["bytes"] == 4:
            names.append(attr["name"])
        elif attr["bytes"] == 8:
            names += [attr["name"] + "_hi", attr["name"] + "_lo"]
        else:
            raise ValueError(f"attribute {attr['name']}: bytes must be 4 or 8")
    return names


def _attribute(rng, rows: int, attr: dict) -> dict:
    v = jax.random.randint(rng, (rows,), attr["low"], attr["high"] + 1,
                           jnp.int32) * attr.get("multiplier", 1)
    if attr["bytes"] == 4:
        return {attr["name"]: v}
    return {attr["name"] + "_hi": jnp.where(v < 0, -1, 0).astype(jnp.int32),
            attr["name"] + "_lo": v}


def _tables(config: dict, rng, key_rng) -> dict:
    tables = config["tables"]
    out = {}
    for t, name in enumerate(sorted(tables)):
        spec, trng = tables[name], jax.random.fold_in(rng, t)
        key = spec["key"]
        if key["kind"] == "primary":
            keys = jnp.arange(spec["rows"], dtype=jnp.int32)
        elif key["kind"] == "foreign":
            domain = (key["domain"] if "domain" in key
                      else tables[key["references"]]["rows"])
            keys = jax.random.randint(jax.random.fold_in(key_rng, t), (spec["rows"],),
                                      0, domain, jnp.int32)
        else:
            raise ValueError(f"table {name}: key kind {key['kind']!r}")
        cols = {key["name"]: jax.random.permutation(jax.random.fold_in(trng, 0), keys)}
        for a, attr in enumerate(spec["attributes"]):
            cols.update(_attribute(jax.random.fold_in(trng, a + 1),
                                   spec["rows"], attr))
        out[name] = {c: cols[c] for c in column_names(spec)}
    return out


def generate(config: dict, seed: int) -> dict[str, dict[str, jax.Array]]:
    """{table: {column: int32 device array}} for `config`, from `seed`."""
    return jax.jit(lambda rng, key_rng: _tables(config, rng, key_rng))(
        jax.random.key(seed), jax.random.key(KEY_SEED))
