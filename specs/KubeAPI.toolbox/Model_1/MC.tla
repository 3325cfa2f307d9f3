---- MODULE MC ----
EXTENDS KubeAPI, TLC

\* CONSTANT definitions @modelParameterConstants:1REQUESTS_CAN_FAIL
const_106000 ==
TRUE
----

\* CONSTANT definitions @modelParameterConstants:2REQUESTS_CAN_TIMEOUT
const_107000 ==
TRUE
----

=============================================================================
\* The reference's Model_1 boundary (JohnStrunk/tla-kubernetes
\* KubeAPI.toolbox/Model_1): both fault constants TRUE.
