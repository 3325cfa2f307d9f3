---- MODULE MC ----
(* The model of PaxosCommit.tla this repo checks: the published            *)
(* PaxosCommit.cfg's constants, invariant and specification, and the       *)
(* module's own closing theorem `PCSpec => TC!TCSpec` as the PROPERTY.  A  *)
(* cfg cannot spell `!`, so the two names below stand for the instanced    *)
(* module's formulas (the Toolbox writes such definitions for the user).   *)
(* Both names are this repo's (benchmark/configs/paxoscommit-mc.json,      *)
(* `assumed.model`).                                                       *)
EXTENDS PaxosCommit

TCSpec == TC!TCSpec

TCConsistent == TC!TCConsistent
====
