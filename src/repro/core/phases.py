"""The decode step's phases, named on the device.

Each name is a ``jax.named_scope``: every op traced under it carries the
name in the ``op_name`` of its HLO metadata
(``jit(<lambda>)/while/body/.../pariskv.select/...``), so the compiled
program and a device trace can charge each op to a phase. The names are
defined here and nowhere else.

An op belongs to the **outermost** name on its path. The decode phases
never nest in one another; everything the chunked prefill runs inside a
mixed step sits under ``FILL``, whatever it calls.

``SUB`` names split a phase further without changing its partition: each
is only ever opened inside ``DENSE`` (or ``FILL``, when the fill runs
the same code), so a path reads ``model.dense/moe.experts/...`` and the
op still belongs to ``DENSE``.
"""
TIER_TABLE = "pariskv.tier_table"   # encode_query, centroid scores, tier weights
STAGE1 = "pariskv.stage1"           # collision scoring (kernel or twin)
SELECT = "pariskv.select"           # top-C candidates and their pool rows
STAGE2 = "pariskv.stage2"           # rerank, mask, final top-k, winner rows
ATTEND = "pariskv.attend"           # K/V append, winner gather, attention
PROMOTE = "pariskv.promote"         # block encode + histogram update
DENSE = "model.dense"               # embed, norms, projections, MLP, head
FILL = "model.fill"                 # the chunked-prefill branch

MOE_ROUTE = "moe.route"             # router logits, top-k gates, dispatch order
MOE_EXPERTS = "moe.experts"         # routed experts' grouped matmuls, combine
MOE_SHARED = "moe.shared"           # shared experts
MLA_LATENT = "mla.latent"           # latent down-projection, W_UK absorb, W_UV

DECODE = (TIER_TABLE, STAGE1, SELECT, STAGE2, ATTEND, PROMOTE, DENSE)
ALL = DECODE + (FILL,)
SUB = (MOE_ROUTE, MOE_EXPERTS, MOE_SHARED, MLA_LATENT)
