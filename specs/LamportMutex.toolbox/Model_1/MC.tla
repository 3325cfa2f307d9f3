---- MODULE MC ----
(* The model of LamportMutex.tla (after the source's MCLamportMutex.tla):    *)
(* the module's own comment asks for Clock to be overridden by a finite set  *)
(* "so that TLC can evaluate the definition of Message".                     *)
EXTENDS LamportMutex, TLC

MCClock == 1 .. maxClock+1

====
