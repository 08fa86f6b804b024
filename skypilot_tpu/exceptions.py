"""Typed exception hierarchy for skypilot-tpu.

Capability parity with the reference's error classes (sky/exceptions.py), but
organized around TPU-native failure modes: slice stockouts, queued-resource
timeouts, and preemption of whole pod slices rather than single VMs.
"""
from __future__ import annotations

from typing import List, Optional


class SkyTpuError(Exception):
    """Base class for all framework errors."""


# --- data model / validation -------------------------------------------------
class InvalidTaskError(SkyTpuError):
    """Task YAML / construction is invalid."""


class InvalidRequestError(SkyTpuError):
    """API request body failed schema validation (HTTP 400)."""


class InvalidResourcesError(SkyTpuError):
    """Resources spec is invalid (unknown accelerator, bad topology...)."""


class InvalidAcceleratorError(InvalidResourcesError):
    """Accelerator string could not be parsed or is unknown to the registry."""


class InvalidInfraError(InvalidResourcesError):
    """`infra:` string (cloud/region/zone) could not be parsed."""


class InvalidSkyConfigError(SkyTpuError):
    """Layered config file failed schema validation."""


class UserRequestRejectedByPolicy(SkyTpuError):
    """The configured admin policy rejected this request
    (parity: sky/exceptions.py UserRequestRejectedByPolicy)."""


class PermissionDeniedError(SkyTpuError):
    """RBAC: the acting user's role does not allow this operation
    (parity: sky/users/permission.py checks)."""


class InvalidDagError(SkyTpuError):
    """DAG has cycles or otherwise cannot be scheduled."""


# --- optimizer / catalog -----------------------------------------------------
class ResourcesUnavailableError(SkyTpuError):
    """No cloud/region/zone can satisfy the resource request.

    Mirrors reference `ResourcesUnavailableError` (sky/exceptions.py) raised by
    the optimizer and the failover provisioner.
    """

    def __init__(self, message: str, *,
                 failover_history: Optional[List[Exception]] = None) -> None:
        super().__init__(message)
        self.failover_history: List[Exception] = failover_history or []

    def with_failover_history(
            self, history: List[Exception]) -> 'ResourcesUnavailableError':
        self.failover_history = history
        return self


class ResourcesMismatchError(SkyTpuError):
    """Requested resources do not match the existing cluster's resources."""


class NoCloudAccessError(SkyTpuError):
    """No cloud is enabled/authenticated (analog of `sky check` failure)."""


# --- provisioning ------------------------------------------------------------
class ProvisionError(SkyTpuError):
    """Base for provisioning failures; carries blocklist classification."""

    #: If True the failover engine should blocklist the whole region, not
    #: just the zone that failed.
    blocklist_region: bool = False


class InsufficientCapacityError(ProvisionError):
    """TPU stockout in a zone (GCE code ZONE_RESOURCE_POOL_EXHAUSTED /
    TPU API RESOURCE_EXHAUSTED).  Retry in the next zone."""


class QuotaExceededError(ProvisionError):
    """Project quota exhausted for this accelerator in this region."""
    blocklist_region = True


class QueuedResourceTimeoutError(ProvisionError):
    """Queued-resource request did not become ACTIVE within the deadline."""


class ClusterSetupError(SkyTpuError):
    """Runtime bootstrap (agent install, env setup) failed on a slice host."""


class HeadNodeUnreachableError(SkyTpuError):
    """Cannot reach the head host of a cluster (SSH/agent probe failed)."""


# --- cluster lifecycle -------------------------------------------------------
class ClusterNotUpError(SkyTpuError):
    """Operation requires a running cluster."""


class ClusterDoesNotExistError(SkyTpuError):
    """Named cluster not found in the global state."""


class ClusterOwnerIdentityMismatchError(SkyTpuError):
    """Current cloud identity differs from the cluster creator's."""


class NotSupportedError(SkyTpuError):
    """Operation unsupported for this cloud/resource combination.

    e.g. `stop` on a multi-host TPU pod slice: TPU pods cannot be stopped,
    only deleted (reference: sky/clouds/gcp.py:219-226).
    """


class PortDoesNotExistError(SkyTpuError):
    """Requested port was never opened on the cluster."""


# --- jobs / execution --------------------------------------------------------
class JobNotFoundError(SkyTpuError):
    """Job id not present in the cluster job queue."""


class JobExitNonZeroError(SkyTpuError):
    """Remote job finished with a non-zero exit code."""

    def __init__(self, message: str, returncode: int = 1) -> None:
        super().__init__(message)
        self.returncode = returncode


class ManagedJobReachedMaxRetriesError(SkyTpuError):
    """Managed job recovery gave up after max restarts."""


class ManagedJobStatusError(SkyTpuError):
    """Managed job is in an unexpected state."""


# --- serve -------------------------------------------------------------------
class ServeError(SkyTpuError):
    """Serve operation failed (unknown service, duplicate name, ...)."""


class ServeUserTerminatedError(SkyTpuError):
    """Service was torn down by the user while an operation was in flight."""


# --- storage -----------------------------------------------------------------
class StorageError(SkyTpuError):
    """Base storage error."""


class StorageBucketCreateError(StorageError):
    pass


class StorageBucketGetError(StorageError):
    pass


class StorageUploadError(StorageError):
    pass


# --- API server --------------------------------------------------------------
class ApiServerError(SkyTpuError):
    """Server-side failure surfaced to the SDK."""


class RequestCancelledError(SkyTpuError):
    """An async API request was cancelled before completion."""


class ApiVersionMismatchError(SkyTpuError):
    """Client/server API version negotiation failed."""


class CommandError(SkyTpuError):
    """A remote/local command failed (analog of reference CommandError)."""

    def __init__(self, returncode: int, command: str, error_msg: str = '',
                 detailed_reason: str = '') -> None:
        self.returncode = returncode
        self.command = command
        self.error_msg = error_msg
        self.detailed_reason = detailed_reason
        if len(command) > 100:
            command = command[:100] + '...'
        super().__init__(
            f'Command {command} failed with return code {returncode}.'
            f'\n{error_msg}')


def format_failover_history(history: List[Exception]) -> str:
    """Render the failover history for user-facing error messages."""
    if not history:
        return ''
    lines = ['Failover history:']
    for i, exc in enumerate(history):
        lines.append(f'  [{i + 1}] {type(exc).__name__}: {exc}')
    return '\n'.join(lines)
