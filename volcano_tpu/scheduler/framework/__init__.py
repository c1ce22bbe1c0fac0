"""Scheduler framework: session lifecycle, tiered plugin dispatch,
transactional statements, and the plugin/action registries."""

from volcano_tpu.scheduler.framework.interface import Action, Plugin
from volcano_tpu.scheduler.framework.plugins import (
    get_action,
    get_plugin_builder,
    register_action,
    register_plugin_builder,
)
from volcano_tpu.scheduler.framework.arguments import Arguments
from volcano_tpu.scheduler.framework.event_handlers import Event, EventHandler
from volcano_tpu.scheduler.framework.session import Session
from volcano_tpu.scheduler.framework.statement import Statement
from volcano_tpu.scheduler.framework.framework import (
    action_span,
    open_session,
    close_session,
    run_action,
    run_actions,
    takeover_recovery_sweep,
)
