---- MODULE TwoPhase ----
(***************************************************************************)
(* Two-phase commit with a record-valued message pool - written in plain   *)
(* TLA+ (heterogeneous records, set-valued state, subset tests), NOT in    *)
(* the gen-frontend subset: this family exercises the structural frontend  *)
(* on a spec it did not birth (VERDICT r4 item 8).  A transaction manager  *)
(* collects readiness votes from resource managers and broadcasts the      *)
(* verdict; resource managers may unilaterally abort while still working.  *)
(* This repo's own module: NOT tlaplus/Examples' TwoPhase.tla, whose       *)
(* family (transaction_commit) sits in specs/PaxosCommit.toolbox.          *)
(***************************************************************************)
EXTENDS Naturals, FiniteSets, TLC

CONSTANTS RM

VARIABLES rmState, tmState, tmPrepared, msgs

vars == <<rmState, tmState, tmPrepared, msgs>>

Init == /\ rmState = [r \in RM |-> "working"]
        /\ tmState = "running"
        /\ tmPrepared = {}
        /\ msgs = {}

(* a resource manager votes to commit and tells the TM *)
Vote(r) == /\ rmState[r] = "working"
           /\ rmState' = [rmState EXCEPT ![r] = "prepared"]
           /\ msgs' = msgs \cup {[kind |-> "vote", from |-> r]}
           /\ UNCHANGED <<tmState, tmPrepared>>

(* a resource manager gives up before voting *)
Renege(r) == /\ rmState[r] = "working"
             /\ rmState' = [rmState EXCEPT ![r] = "aborted"]
             /\ UNCHANGED <<tmState, tmPrepared, msgs>>

(* the TM registers a vote message *)
Collect(r) == /\ tmState = "running"
              /\ [kind |-> "vote", from |-> r] \in msgs
              /\ tmPrepared' = tmPrepared \cup {r}
              /\ UNCHANGED <<rmState, tmState, msgs>>

(* every vote is in: broadcast commit *)
Decide == /\ tmState = "running"
          /\ tmPrepared = RM
          /\ tmState' = "committed"
          /\ msgs' = msgs \cup {[kind |-> "commit"]}
          /\ UNCHANGED <<rmState, tmPrepared>>

(* the TM may abort any time before deciding *)
CallOff == /\ tmState = "running"
           /\ tmState' = "aborted"
           /\ msgs' = msgs \cup {[kind |-> "stop"]}
           /\ UNCHANGED <<rmState, tmPrepared>>

(* resource managers obey the broadcast verdict *)
ObeyCommit(r) == /\ [kind |-> "commit"] \in msgs
                 /\ rmState[r] = "prepared"
                 /\ rmState' = [rmState EXCEPT ![r] = "committed"]
                 /\ UNCHANGED <<tmState, tmPrepared, msgs>>

ObeyAbort(r) == /\ [kind |-> "stop"] \in msgs
                /\ rmState[r] # "committed"
                /\ rmState[r] # "aborted"
                /\ rmState' = [rmState EXCEPT ![r] = "aborted"]
                /\ UNCHANGED <<tmState, tmPrepared, msgs>>

Next == \/ Decide
        \/ CallOff
        \/ \E r \in RM : \/ Vote(r)
                         \/ Renege(r)
                         \/ Collect(r)
                         \/ ObeyCommit(r)
                         \/ ObeyAbort(r)

Spec == /\ Init
        /\ [][Next]_vars

TypeOK == /\ rmState \in [RM -> {"working", "prepared", "committed",
                                 "aborted"}]
          /\ tmState \in {"running", "committed", "aborted"}
          /\ tmPrepared \subseteq RM
          /\ \A m \in msgs : m.kind \in {"vote", "commit", "stop"}

(* the classic 2PC safety property: no split verdict *)
Agreement == \A r1, r2 \in RM : ~(/\ rmState[r1] = "aborted"
                                  /\ rmState[r2] = "committed")

(* the TM only commits on unanimous votes *)
CommitVoted == tmState = "committed" => tmPrepared = RM
====
