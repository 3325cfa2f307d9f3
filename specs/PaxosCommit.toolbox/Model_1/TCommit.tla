------------------------------ MODULE TCommit ------------------------------
(***************************************************************************)
(* Transaction commit, the abstract specification: the module of Gray and  *)
(* Lamport, "Consensus on Transaction Commit" (ACM TODS 31(1), 2006), as   *)
(* tlaplus/Examples publishes it under specifications/transaction_commit.  *)
(* Recalled (benchmark/configs/paxoscommit-mc.json, `assumed.module`):     *)
(* the equations are the source's, the comments the source's in substance. *)
(*                                                                         *)
(* A set RM of resource managers each moves from "working" through         *)
(* "prepared" to "committed", or to "aborted"; no two of them ever         *)
(* disagree on the outcome.  TwoPhase and PaxosCommit implement this       *)
(* specification: `THEOREM PCSpec => TC!TCSpec` in PaxosCommit.tla.        *)
(***************************************************************************)
CONSTANT RM       \* The set of participating resource managers

VARIABLE rmState  \* rmState[r] is the state of resource manager r.

TCTypeOK ==
  (*************************************************************************)
  (* The type-correctness invariant                                        *)
  (*************************************************************************)
  rmState \in [RM -> {"working", "prepared", "committed", "aborted"}]

TCInit == rmState = [r \in RM |-> "working"]
  (*************************************************************************)
  (* The initial predicate.                                                *)
  (*************************************************************************)

canCommit == \A r \in RM : rmState[r] \in {"prepared", "committed"}
  (*************************************************************************)
  (* True iff all RMs are in the "prepared" or "committed" state.          *)
  (*************************************************************************)

notCommitted == \A r \in RM : rmState[r] # "committed"
  (*************************************************************************)
  (* True iff no resource manager has decided to commit.                   *)
  (*************************************************************************)

(***************************************************************************)
(* The actions that may be performed by the RMs, and then the next-state   *)
(* relation.                                                               *)
(***************************************************************************)
Prepare(r) == /\ rmState[r] = "working"
              /\ rmState' = [rmState EXCEPT ![r] = "prepared"]

Decide(r)  == \/ /\ rmState[r] = "prepared"
                 /\ canCommit
                 /\ rmState' = [rmState EXCEPT ![r] = "committed"]
              \/ /\ rmState[r] \in {"working", "prepared"}
                 /\ notCommitted
                 /\ rmState' = [rmState EXCEPT ![r] = "aborted"]

TCNext == \E r \in RM : Prepare(r) \/ Decide(r)
  (*************************************************************************)
  (* The next-state action.                                                *)
  (*************************************************************************)

TCConsistent ==
  (*************************************************************************)
  (* A state predicate asserting that two RMs have not arrived at          *)
  (* conflicting decisions.  It is an invariant of the specification.      *)
  (*************************************************************************)
  \A r1, r2 \in RM : ~ /\ rmState[r1] = "aborted"
                       /\ rmState[r2] = "committed"

TCSpec == TCInit /\ [][TCNext]_rmState
  (*************************************************************************)
  (* The complete specification of the protocol written as a temporal      *)
  (* formula.                                                              *)
  (*************************************************************************)

THEOREM TCSpec => [](TCTypeOK /\ TCConsistent)
  (*************************************************************************)
  (* This theorem asserts the truth of the temporal formula whose meaning  *)
  (* is that the state predicate TCTypeOK /\ TCConsistent is an invariant  *)
  (* of the specification TCSpec.                                          *)
  (*************************************************************************)
=============================================================================
