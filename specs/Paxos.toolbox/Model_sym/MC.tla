---- MODULE MC ----
(* The model of Paxos.tla this repo checks (after the source's MCPaxos.tla): *)
(* three acceptors, two values, the majority quorums, a finite ballot range, *)
(* and agreement over the 2b messages in place of the source's refinement    *)
(* theorem.  Everything here is this repo's (benchmark/configs/paxos-mc.json, *)
(* `assumed`).                                                               *)
EXTENDS Paxos, TLC

CONSTANTS a1, a2, a3

CONSTANTS v1, v2

MCQuorum == {{a1, a2}, {a1, a3}, {a2, a3}}

MCBallot == 0..3

(* the source's MCPaxos.tla: the symmetry set MC.cfg's SYMMETRY names *)
MCSymmetry == Permutations(Acceptor) \cup Permutations(Value)

ChosenAt(b, v) ==
  \E Q \in Quorum :
    \A a \in Q : [type |-> "2b", acc |-> a, bal |-> b, val |-> v] \in msgs

Agreement ==
  \A b1, b2 \in Ballot, v1x, v2x \in Value :
    ChosenAt(b1, v1x) /\ ChosenAt(b2, v2x) => v1x = v2x
====
