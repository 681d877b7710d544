"""Typed cluster-event strings that wake unschedulable pods.

Reference: /root/reference/pkg/scheduler/internal/queue/events.go:20-72.
"""

PodAdd = "PodAdd"
NodeAdd = "NodeAdd"
NodeDelete = "NodeDelete"
ScheduleAttemptFailure = "ScheduleAttemptFailure"
BackoffComplete = "BackoffComplete"
UnschedulableTimeout = "UnschedulableTimeout"
AssignedPodAdd = "AssignedPodAdd"
AssignedPodUpdate = "AssignedPodUpdate"
AssignedPodDelete = "AssignedPodDelete"
# an assumed pod gave its node back without ever being bound (a Permit
# wait that ended in a rejection or a timeout, a failed bind): room is
# free as after a delete (not in the reference, whose parked pods wait
# for the periodic flush)
AssumedPodForget = "AssumedPodForget"
PvAdd = "PvAdd"
PvUpdate = "PvUpdate"
PvcAdd = "PvcAdd"
PvcUpdate = "PvcUpdate"
StorageClassAdd = "StorageClassAdd"
ServiceAdd = "ServiceAdd"
ServiceUpdate = "ServiceUpdate"
ServiceDelete = "ServiceDelete"
CSINodeAdd = "CSINodeAdd"
CSINodeUpdate = "CSINodeUpdate"
NodeSpecUnschedulableChange = "NodeSpecUnschedulableChange"
NodeAllocatableChange = "NodeAllocatableChange"
NodeLabelChange = "NodeLabelChange"
NodeTaintChange = "NodeTaintChange"
NodeConditionChange = "NodeConditionChange"
