"""Lowering: device ms per forward under the scopes of ResNet-18's 7x7/2
stem and 3x3/2 stage transitions, whatever path the planner gives them."""

SCOPES = ("stem", "s1b0.conv1", "s2b0.conv1", "s3b0.conv1")


def read(rec):
    if rec.get("kind") != "offline":
        return None
    scope_s = rec["trace"]["scope_s"]
    if not all(s in scope_s for s in SCOPES) or not rec["forwards"]:
        return None
    return 1e3 * sum(scope_s[s] for s in SCOPES) / rec["forwards"]
