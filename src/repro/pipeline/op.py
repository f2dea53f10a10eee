"""Declarative operating points for the BaF compression pipeline.

An :class:`OperatingPoint` is the single value object that names *everything*
about how one request's split activation is coded on the wire: how many
channels travel (C), the quantizer depth (n), and which wire-profile
generation the container speaks. The entropy coder is always static rANS on
the channel-last tensor (repro.codec).

Capability negotiation lets a gateway refuse — or, when allowed, downgrade —
an operating point whose wire profile or bit depth it does not speak,
instead of failing deep inside the codec on the cloud side.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

# Wire-profile generation: bumped with the container magic (core/codec.py
# writes BaF2). A gateway advertises the profiles it can decode; encode and
# decode sides must agree before any bytes move.
WIRE_PROFILE_VERSION = 2

# Streaming-session wire profile: the SessionFrame framing that wraps I/P
# frames (repro.session.codec writes SSF1). Negotiated separately from the
# container profile — an endpoint may decode plain containers but not speak
# the temporal-delta framing, in which case sessions fall back to I-only.
SESSION_WIRE_VERSION = 1


class NegotiationError(ValueError):
    """The gateway cannot serve this operating point and may not downgrade."""


@dataclass(frozen=True)
class OperatingPoint:
    """One coding configuration, end to end.

    c        : transmitted channels
    bits     : quantizer depth n
    backend  : the entropy coder; only ``"rans"`` (static tables) exists
    profile  : wire-profile generation this point's containers speak
    """
    c: int
    bits: int
    backend: str = "rans"
    profile: int = WIRE_PROFILE_VERSION

    def __post_init__(self):
        if self.c < 1:
            raise ValueError(f"c must be >= 1, got {self.c}")
        if not 1 <= self.bits <= 16:
            raise ValueError(f"bits must be in 1..16, got {self.bits}")
        if self.backend != "rans":
            raise ValueError(f"unknown backend {self.backend!r}; the only "
                             f"entropy coder is 'rans'")


@dataclass(frozen=True)
class Capabilities:
    """What one gateway (or decoder) can speak.

    profiles  : wire-profile generations the decode side understands
    max_bits  : deepest quantizer it will decode
    downgrade : whether :func:`negotiate` may substitute a shallower bit
                depth instead of refusing
    session_profiles : SessionFrame framing generations the decode side
                speaks (empty tuple = no temporal P-frames; sessions run
                I-only when downgrade is allowed)
    task_heads : downstream task heads this endpoint serves (None = every
                registered head; see repro.tasks.heads). A declared task
                the endpoint does not serve is dropped when downgrade is
                allowed, refused otherwise (:func:`negotiate_tasks`)
    """
    profiles: tuple = (WIRE_PROFILE_VERSION,)
    max_bits: int = 16
    downgrade: bool = True
    session_profiles: tuple = (SESSION_WIRE_VERSION,)
    task_heads: tuple | None = None

    def serves_task(self, name: str) -> bool:
        return self.task_heads is None or name in self.task_heads


def negotiate(op: OperatingPoint, caps: Capabilities | None) -> OperatingPoint:
    """Fit ``op`` to ``caps``: pass through, downgrade, or refuse.

    A wire-profile mismatch always refuses — there is no lower profile to
    fall back to, the container format itself is foreign. The bit depth
    downgrades to the capabilities' max depth when ``caps.downgrade`` allows
    it, otherwise :class:`NegotiationError` is raised.
    """
    if caps is None:
        return op
    if op.profile not in caps.profiles:
        raise NegotiationError(
            f"gateway speaks wire profiles {caps.profiles}, operating point "
            f"requires profile {op.profile}")
    if op.bits > caps.max_bits:
        if not caps.downgrade:
            raise NegotiationError(
                f"gateway decodes at most {caps.max_bits} bits, operating "
                f"point requires {op.bits}")
        return dataclasses.replace(op, bits=caps.max_bits)
    return op


def negotiate_session(caps: Capabilities | None, *,
                      profile: int = SESSION_WIRE_VERSION) -> bool:
    """Can a session stream temporal P-frames at this endpoint?

    True = the decode side speaks the SessionFrame profile, P-frames may
    flow. False = it does not, but downgrade is allowed, so the session runs
    I-frame-only (every frame a standalone container — correct, just more
    bits). Refusal (profile unknown AND downgrade disabled) raises
    :class:`NegotiationError` before any frame is encoded.
    """
    if caps is None or profile in caps.session_profiles:
        return True
    if caps.downgrade:
        return False
    raise NegotiationError(
        f"endpoint speaks session profiles {caps.session_profiles}, stream "
        f"requires profile {profile} and downgrade is disabled")


def negotiate_tasks(tasks, caps: Capabilities | None) -> tuple:
    """Fit a tenant's declared task set to the endpoint's served heads.

    Returns the effective task tuple (declaration order kept, duplicates
    dropped). A declared head the endpoint does not serve is dropped when
    ``caps.downgrade`` allows it — the tenant is served the subset and,
    through bit allocation, only pays for that subset; with downgrade
    disabled, or when nothing declared survives, the whole declaration is
    refused (:class:`NegotiationError`). Task negotiation never touches the
    operating point — wire-profile and bit-depth fitting stay in
    :func:`negotiate`, so a foreign wire profile still refuses regardless
    of how few heads a tenant declares.
    """
    declared = tuple(dict.fromkeys(tasks))
    if not declared:
        raise ValueError("empty task declaration (declare at least one "
                         "task head)")
    if caps is None or caps.task_heads is None:
        return declared
    served = tuple(t for t in declared if t in caps.task_heads)
    if served == declared:
        return declared
    dropped = [t for t in declared if t not in caps.task_heads]
    if not caps.downgrade:
        raise NegotiationError(
            f"endpoint serves task heads {sorted(caps.task_heads)}, tenant "
            f"declared unsupported {dropped} and downgrade is disabled")
    if not served:
        raise NegotiationError(
            f"endpoint serves task heads {sorted(caps.task_heads)}; none of "
            f"the declared tasks {list(declared)} can be served")
    return served
