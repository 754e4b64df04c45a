"""Ordered regex partition rules mapping parameter names to
`jax.sharding.PartitionSpec` (reference idiom: fmengine-style
`match_partition_rules`; the paper-side motivation is
arXiv:2004.13336 — shard the state, not just the work).

A rule set is an ordered sequence of ``(pattern, spec)`` pairs. Matching
is `re.search` (substring) — anchor with ``^``/``$`` for exact names —
and the FIRST matching rule wins, so order encodes precedence: put the
specific attention/ffn rules above the catch-all ``_weight$`` rule. A
spec of ``None`` means "replicate this parameter" (the explicit
fallback rule ``(".*", None)`` ends every validated rule set).

Specs are written against the canonical 2-D mesh axes (`'dp'`, `'tp'` —
see shard/mesh.py); a rule may name any axis of the mesh the plan is
built over. A matched spec is then NORMALISED against the concrete
parameter shape (`normalize_spec`): entries beyond the array's rank are
dropped, axes of size 1 collapse to replicated, and a dimension that the
named axis does not divide falls back to replicated FOR THAT DIMENSION —
every such downgrade is recorded in the plan's `fallbacks` report
instead of failing (a model-zoo net with one odd head must still train,
just less sharded).

`DEFAULT_RULES` covers the model zoo's naming scheme (Dense/Conv:
``<block>N_weight``/``_bias``; norms: ``_gamma``/``_beta``/
``running_*``; transformer/BERT: ``..._qkv_weight``, ``..._proj_weight``,
``..._ffn1_weight`` ...): matmul weights that benefit from tensor
parallelism shard their output dim over ``tp``; embeddings row-shard the
vocab over ``tp``; every other weight FSDP-shards dim 0 over ``dp``;
biases and norm parameters replicate (they are small and their update
cost is noise).
"""
from __future__ import annotations

import re

import numpy as np
from jax.sharding import PartitionSpec as P

from ..base import MXNetError

__all__ = ["DEFAULT_RULES", "EMBED_WEIGHT_PATTERN",
           "EXPERT_WEIGHT_PATTERN",
           "match_partition_rules", "validate_rules",
           "normalize_spec", "spec_to_json", "spec_from_json",
           "rules_to_json", "rules_from_json"]


# What counts as an embedding table, BY NAME: either "embed" ANYWHERE
# in the final segment (zoo/transformer "embed*"/"embedding*",
# "wordembed0"/"posembed" compound names, `ShardedEmbedding`'s
# "shardedembedding*" — the pre-ISSUE-15 rule's reach, kept so no
# existing model silently loses its sharding) or a segment STARTING
# with "emb" (DLRM-style "emb0"/"emb_cat3") — while "member0_weight"
# (no "embed", "emb" mid-word) stays a plain Dense weight. ONE
# definition shared by the DEFAULT_RULES row-shard rule below and the
# recommender memory headline (shard/embedding.py
# `embed_param_bytes_frac`).
EMBED_WEIGHT_PATTERN = r"(?:embed[^/]*|(?:^|_)emb[^/]*)_weight$"

# What counts as an expert bank, BY NAME: `ShardedMoE`'s stacked
# ``expert_ffn*_weight`` / ``_bias`` parameters (dim 0 is the expert
# index on every one of them — weights AND biases shard together, so a
# shard owns its experts whole). Shared by the DEFAULT_RULES expert
# rule and `ShardPlan._check_large_replicated`'s expert-bank warning.
EXPERT_WEIGHT_PATTERN = r"(?:^|_)expert[^/]*_(?:weight|bias)$"


# First match wins. The attention/ffn rules sit ABOVE the generic
# ``_weight$`` catch-all; the final (".*", None) makes the replicated
# fallback explicit (an unmatched name never errors, it replicates and
# lands in the report).
#
# A rule's spec may also be a BARE AXIS NAME string — shorthand for
# "row-shard dim 0 over that axis" (``P(axis)``), the per-param axis
# override syntax. Unlike PartitionSpec rules (whose unknown axes
# downgrade to replicated with a fallback report), a string override
# is explicit user intent: `ShardPlan` validates it against the mesh
# and raises on an axis the mesh does not have.
DEFAULT_RULES = (
    # expert banks (ShardedMoE): dim 0 is the expert index — shard it
    # over tp (the axis-override shorthand, dogfooded) so each device
    # holds E/tp experts; biases included, see EXPERT_WEIGHT_PATTERN.
    # Sits ABOVE the bias-replicate rule on purpose.
    (EXPERT_WEIGHT_PATTERN, "tp"),
    # MoE router: (E, d), tiny, every device gates locally — replicate
    (r"(?:^|_)gate_weight$", None),
    # norm statistics / affine params + biases: tiny, replicate
    (r"_(gamma|beta|running_mean|running_var|bias|scales)$", None),
    # embedding tables: row-shard the vocab dim over tp. Under a
    # captured step a `ShardedEmbedding` table with this layout takes
    # the sparse fast path (shard/embedding.py: bucketed all-to-all
    # lookup + scatter-add update); anything else lets GSPMD insert
    # the exchange.
    (EMBED_WEIGHT_PATTERN, P("tp", None)),
    # attention + ffn matmul weights: TP over the output dim (Dense
    # weights are (out, in) — dim 0 is the output features)
    (r"(?:^|_)(qkv|query|key|value|proj|q|k|v|out|ffn[0-9]*)_weight$",
     P("tp", None)),
    # everything else with a weight: FSDP row-shard over dp
    (r"_weight$", P("dp", None)),
    # explicit replicated fallback
    (r".*", None),
)


def validate_rules(rules, mesh=None):
    """Compile and sanity-check an ordered rule set. Returns a tuple of
    ``(compiled_regex, spec)`` pairs; raises MXNetError on an invalid
    pattern or a spec that is none of: None, a PartitionSpec, a plain
    tuple of axis names (converted), or a bare axis-name STRING — the
    per-param axis override, shorthand for ``P(axis)`` (row-shard dim 0
    over that axis). When ``mesh`` is given, every string override is
    validated against its axis names and an unknown axis raises — an
    explicit override silently replicating would be the one downgrade
    the fallback report cannot excuse."""
    mesh_axes = None if mesh is None else set(mesh.shape)
    out = []
    for i, item in enumerate(rules):
        try:
            pattern, spec = item
        except (TypeError, ValueError):
            raise MXNetError(f"rule {i}: expected (pattern, spec) pair, "
                             f"got {item!r}")
        try:
            rx = re.compile(pattern)
        except re.error as e:
            raise MXNetError(f"rule {i}: bad regex {pattern!r}: {e}")
        if isinstance(spec, str):
            if mesh_axes is not None and spec not in mesh_axes:
                raise MXNetError(
                    f"rule {i} ({pattern!r}): axis override {spec!r} "
                    f"names no axis of the mesh "
                    f"(axes: {sorted(mesh_axes)})")
            spec = P(spec)
        elif spec is not None and not isinstance(spec, P):
            if isinstance(spec, (tuple, list)):
                # the TUPLE form of the axis override (ISSUE 19
                # satellite): per-dim entries shard dim 1 / both dims of
                # a table — e.g. ("tp", "dp") or (None, "tp"). Like the
                # bare string it is an explicit override, so every named
                # axis must exist on the mesh (divisibility still
                # downgrades per-shape through normalize_spec — a hard
                # error there would break partial batches)
                for d, entry in enumerate(spec):
                    if entry is None:
                        continue
                    names = entry if isinstance(entry, (tuple, list)) \
                        else (entry,)
                    for nm in names:
                        if not isinstance(nm, str):
                            raise MXNetError(
                                f"rule {i} ({pattern!r}): tuple spec "
                                f"entry {d} must be None, an axis name, "
                                f"or a tuple of axis names, got "
                                f"{entry!r}")
                        if mesh_axes is not None and nm not in mesh_axes:
                            raise MXNetError(
                                f"rule {i} ({pattern!r}): tuple spec "
                                f"entry {d} names axis {nm!r} which is "
                                f"no axis of the mesh "
                                f"(axes: {sorted(mesh_axes)})")
                spec = P(*spec)
            else:
                raise MXNetError(f"rule {i} ({pattern!r}): spec must be a "
                                 f"PartitionSpec, tuple, axis-name "
                                 f"string, or None, got {spec!r}")
        out.append((rx, spec))
    return tuple(out)


def _axis_size(mesh, entry):
    """Product of mesh-axis sizes for one spec entry (an axis name or a
    tuple of axis names); raises KeyError on an unknown axis."""
    names = entry if isinstance(entry, (tuple, list)) else (entry,)
    n = 1
    for name in names:
        n *= int(mesh.shape[name])
    return n


def normalize_spec(spec, shape, mesh, name=None, fallbacks=None):
    """Clamp a rule's raw spec to one concrete array: truncate to the
    array's rank, drop axes the mesh sizes at 1, and downgrade any entry
    whose axis product does not divide that dimension to replicated.
    Scalars and single-element arrays always replicate. Each downgrade
    appends ``(name, dim, entry, reason)`` to `fallbacks` when given.
    Returns a PartitionSpec safe to build a NamedSharding from."""
    shape = tuple(int(s) for s in shape)
    if spec is None or len(shape) == 0 or int(np.prod(shape)) <= 1:
        return P()
    entries = list(spec)[:len(shape)]
    out = []
    for dim, entry in enumerate(entries):
        if entry is None:
            out.append(None)
            continue
        try:
            n = _axis_size(mesh, entry)
        except KeyError:
            if fallbacks is not None:
                fallbacks.append((name, dim, entry, "unknown_axis"))
            out.append(None)
            continue
        if n <= 1:
            out.append(None)
            continue
        if shape[dim] % n:
            if fallbacks is not None:
                fallbacks.append((name, dim, entry, "not_divisible"))
            out.append(None)
            continue
        out.append(entry)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def match_partition_rules(rules, named_shapes, mesh=None,
                          on_unmatched="replicate"):
    """Resolve an ordered rule set over ``{name: shape}`` (shapes may be
    arrays or anything with ``.shape``). Returns
    ``(specs, report)`` where `specs` maps every name to its RAW matched
    PartitionSpec (un-normalised unless `mesh` is given) and `report` is
    ``{"unmatched": [names...], "fallbacks": [(name, dim, axis,
    reason)...]}``.

    First matching rule wins (`re.search`). A name no rule matches is
    replicated and recorded under ``unmatched`` (``on_unmatched="error"``
    raises instead — the fmengine behaviour)."""
    compiled = validate_rules(rules, mesh=mesh)
    specs = {}
    report = {"unmatched": [], "fallbacks": []}
    for name, shp in named_shapes.items():
        shape = tuple(getattr(shp, "shape", shp) or ())
        matched = None
        for rx, spec in compiled:
            if rx.search(name) is not None:
                matched = spec
                break
        else:
            if on_unmatched == "error":
                raise MXNetError(f"no partition rule matches parameter "
                                 f"{name!r}")
            report["unmatched"].append(name)
        if mesh is not None:
            matched = normalize_spec(matched, shape, mesh, name=name,
                                     fallbacks=report["fallbacks"])
        elif matched is None:
            matched = P()
        specs[name] = matched
    return specs, report


# ------------------------------------------------- manifest round-trip
def spec_to_json(spec):
    """A PartitionSpec as a JSON-friendly list (axis name, list of axis
    names, or null per dimension) — the manifest.json encoding."""
    out = []
    for entry in tuple(spec or ()):
        if isinstance(entry, (tuple, list)):
            out.append(list(entry))
        else:
            out.append(entry)
    return out


def spec_from_json(data):
    """Inverse of `spec_to_json`."""
    entries = []
    for entry in (data or []):
        if isinstance(entry, list):
            entries.append(tuple(entry))
        else:
            entries.append(entry)
    return P(*entries)


def rules_to_json(rules):
    """An ordered rule set as a JSON-friendly list, round-tripping all
    four spec forms: ``{"pattern": ..., "axis": name}`` for the
    string axis-override shorthand, ``{"pattern": ..., "axes": [...]}``
    for its per-dim TUPLE form, ``{"pattern": ..., "spec": null}``
    for replicate, ``{"pattern": ..., "spec": [...]}``
    (`spec_to_json`) for a PartitionSpec."""
    out = []
    for pattern, spec in rules:
        if isinstance(spec, str):
            out.append({"pattern": pattern, "axis": spec})
        elif spec is None:
            out.append({"pattern": pattern, "spec": None})
        elif isinstance(spec, (tuple, list)) and not isinstance(spec, P):
            out.append({"pattern": pattern, "axes": spec_to_json(spec)})
        else:
            out.append({"pattern": pattern, "spec": spec_to_json(spec)})
    return out


def rules_from_json(data):
    """Inverse of `rules_to_json`. Returns the ``(pattern, spec)``
    tuple form `validate_rules` accepts (axis overrides stay strings
    and tuple overrides stay tuples, so a decode -> encode round-trip
    is byte-identical)."""
    rules = []
    for item in (data or []):
        pattern = item["pattern"]
        if "axis" in item:
            rules.append((pattern, item["axis"]))
        elif "axes" in item:
            rules.append((pattern, tuple(
                tuple(e) if isinstance(e, list) else e
                for e in item["axes"])))
        elif item.get("spec") is None:
            rules.append((pattern, None))
        else:
            rules.append((pattern, spec_from_json(item["spec"])))
    return tuple(rules)
