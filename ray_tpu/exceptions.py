"""User-facing exceptions.

Mirrors the surface of the reference's `ray.exceptions`
(reference: python/ray/exceptions.py — RayError, RayTaskError,
RayActorError, GetTimeoutError, ObjectLostError, WorkerCrashedError,
TaskCancelledError, OutOfMemoryError) so code written against the
reference maps one-to-one.
"""
from __future__ import annotations

from typing import Optional


class RayTpuError(Exception):
    """Base class for all framework errors."""


# Alias matching the reference's naming so users can except the same shape.
RayError = RayTpuError


class TaskError(RayTpuError):
    """A task raised; carries the remote traceback. Re-raised at `get()`.

    Equivalent of the reference's RayTaskError: the remote exception is
    stringified and chained so the driver sees the worker-side stack.
    """

    def __init__(self, function_name: str, traceback_str: str, cause_type: str = ""):
        self.function_name = function_name
        self.traceback_str = traceback_str
        self.cause_type = cause_type
        super().__init__(f"task {function_name} failed:\n{traceback_str}")


RayTaskError = TaskError


class ActorError(RayTpuError):
    """Actor died or its creation failed (reference: RayActorError)."""

    def __init__(self, message: str = "actor died", actor_id: Optional[str] = None):
        self.actor_id = actor_id
        super().__init__(message)


RayActorError = ActorError


class ActorDiedError(ActorError):
    pass


class ActorUnavailableError(ActorError):
    pass


class GetTimeoutError(RayTpuError, TimeoutError):
    pass


class ObjectLostError(RayTpuError):
    def __init__(self, object_id_hex: str, message: str = ""):
        self.object_id_hex = object_id_hex
        super().__init__(message or f"object {object_id_hex} lost and not reconstructable")


class ObjectStoreFullError(RayTpuError):
    pass


class WorkerCrashedError(RayTpuError):
    pass


class TaskCancelledError(RayTpuError):
    pass


class OutOfMemoryError(RayTpuError):
    pass


class RuntimeEnvSetupError(RayTpuError):
    pass


class TPUGrantError(RayTpuError):
    """A worker was granted TPU chips it cannot use: the host exposes no
    such device, or the process had loaded JAX before the grant arrived
    (its backend choice can no longer be changed). The task fails with
    this instead of computing on the CPU under a TPU grant."""


class PlacementGroupError(RayTpuError):
    pass
