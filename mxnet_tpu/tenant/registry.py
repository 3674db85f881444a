"""Tenant registry + the serving-plane facade (mx.tenant).

``TenantPlane`` is the ONE object the serve stack holds: it owns the
tenant table (weights, quotas, adapter bindings), the WFQ virtual
clock (fairsched.py), the usage ledger (quota.py) and — once a
``DecodeRunner`` builds against it — the device-resident adapter bank
(adapters.py).  The decode scheduler asks it three questions: *may
this submission queue?* (``check_submit``), *who is admitted next?*
(``select``), and *what changed?* (``admit_granted`` /
``on_release``); everything else is introspection.
"""
from __future__ import annotations

import threading

from .. import telemetry
from ..base import MXNetError, get_env
from .adapters import AdapterBank, AdapterError, load_adapter
from .fairsched import FairQueue
from .quota import QuotaLedger, TenantQuota

__all__ = ["TenantConfig", "Tenant", "TenantPlane", "UnknownTenant"]


class UnknownTenant(MXNetError):
    """The request names a tenant the registry has never seen — a
    client error (HTTP 400), not backpressure."""


class TenantConfig:
    """Knobs of the multi-tenant plane (README "Multi-tenant
    serving").

    slots : adapter bank capacity (``MXNET_TENANT_SLOTS``).
    max_rank : bank-wide LoRA rank ceiling (``MXNET_TENANT_MAX_RANK``)
        — lower-rank adapters zero-pad, higher-rank ones are rejected.
    default_weight : WFQ weight for tenants that don't set one
        (``MXNET_TENANT_DEFAULT_WEIGHT``).
    max_live / max_pages / queue_depth : default per-tenant quota
        (``MXNET_TENANT_MAX_LIVE`` / ``_MAX_PAGES`` /
        ``_QUEUE_DEPTH``; 0 = unlimited for the first two).
    targets : LoRA target Dense names (None = per-layer q/v).
    """

    def __init__(self, slots=None, max_rank=None, default_weight=None,
                 max_live=None, max_pages=None, queue_depth=None,
                 targets=None):
        self.slots = get_env("MXNET_TENANT_SLOTS", int, 8) \
            if slots is None else int(slots)
        self.max_rank = get_env("MXNET_TENANT_MAX_RANK", int, 8) \
            if max_rank is None else int(max_rank)
        self.default_weight = get_env(
            "MXNET_TENANT_DEFAULT_WEIGHT", float, 1.0) \
            if default_weight is None else float(default_weight)
        self.max_live = get_env("MXNET_TENANT_MAX_LIVE", int, 0) \
            if max_live is None else int(max_live)
        self.max_pages = get_env("MXNET_TENANT_MAX_PAGES", int, 0) \
            if max_pages is None else int(max_pages)
        self.queue_depth = get_env("MXNET_TENANT_QUEUE_DEPTH", int, 16) \
            if queue_depth is None else int(queue_depth)
        self.targets = list(targets) if targets is not None else None
        if self.slots < 1:
            raise ValueError("TenantConfig needs slots >= 1")

    def default_quota(self):
        return TenantQuota(self.max_live, self.max_pages,
                           self.queue_depth)

    def as_dict(self):
        return {"slots": self.slots, "max_rank": self.max_rank,
                "default_weight": self.default_weight,
                "max_live": self.max_live, "max_pages": self.max_pages,
                "queue_depth": self.queue_depth,
                "targets": self.targets}


class Tenant:
    __slots__ = ("name", "weight", "quota", "adapter")

    def __init__(self, name, weight, quota):
        self.name = str(name)
        self.weight = float(weight)
        self.quota = quota
        self.adapter = None       # resident AdapterSpec name (or None)
        if self.weight <= 0:
            raise ValueError("tenant %r: weight must be > 0" % name)

    def as_dict(self):
        return {"name": self.name, "weight": self.weight,
                "quota": self.quota.as_dict(), "adapter": self.adapter}


class TenantPlane:
    """Registry + scheduler + bank facade (module doc)."""

    def __init__(self, config=None):
        self.config = config or TenantConfig()
        self._tenants = {}
        self.fair = FairQueue()
        self.ledger = QuotaLedger()
        self.bank = None          # attached by DecodeRunner via build_bank
        self._lock = threading.RLock()
        self.rejects = {}         # reason -> count
        self.served_tokens = {}   # tenant -> emitted tokens

    # -- registry ------------------------------------------------------------
    def register(self, name, weight=None, quota=None):
        """Register (or re-weight) a tenant; returns it."""
        with self._lock:
            if quota is None:
                q = self.config.default_quota()
            elif isinstance(quota, TenantQuota):
                q = quota
            else:
                q = TenantQuota(**dict(quota))
            t = self._tenants.get(str(name))
            if t is None:
                t = Tenant(name,
                           self.config.default_weight
                           if weight is None else weight, q)
                self._tenants[t.name] = t
            else:
                if weight is not None:
                    t.weight = float(weight)
                t.quota = q
            return t

    def get(self, name):
        t = self._tenants.get(str(name))
        if t is None:
            raise UnknownTenant(
                "unknown tenant %r (registered: %s)"
                % (name, sorted(self._tenants) or "none"))
        return t

    def tenants(self):
        with self._lock:
            return list(self._tenants.values())

    # -- adapter bank --------------------------------------------------------
    def build_bank(self, block):
        """Build (once) the adapter bank for ``block`` — called by
        ``DecodeRunner`` BEFORE warm-up so every program compiles with
        the bank in its signature."""
        with self._lock:
            if self.bank is None:
                self.bank = AdapterBank(block, self.config.slots,
                                        self.config.max_rank,
                                        targets=self.config.targets)
                if telemetry.ENABLED:
                    telemetry.TENANT_SLOTS.set(self.bank.n_slots)
            return self.bank

    def _need_bank(self):
        if self.bank is None:
            raise AdapterError(
                "no adapter bank attached yet — build the DecodeRunner "
                "with tenant=<this plane> first")
        return self.bank

    def load_adapter(self, tenant, root=None, spec=None, step=None,
                     ctx=None):
        """Bind an adapter to ``tenant``: restore it from an
        ``mx.checkpoint`` ``root`` (or take a pre-built ``spec``),
        validate against the bank, and install it into the tenant's
        existing slot (hot swap) or a free one.  Returns the slot."""
        t = self.get(tenant)
        bank = self._need_bank()
        if (root is None) == (spec is None):
            raise AdapterError(
                "load_adapter needs exactly one of root= / spec=")
        if spec is None:
            spec = load_adapter(root, name="%s@%s" % (t.name, root),
                                step=step, ctx=ctx)
        with self._lock:
            slot = bank.slot_of(t.adapter) if t.adapter else -1
            if slot < 0:
                slot = bank.free_slot()
            if slot < 0:
                raise AdapterError(
                    "adapter bank full (%d slots all resident: %s)"
                    % (bank.n_slots, bank.slots))
            bank.load(slot, spec)
            t.adapter = spec.name
        if telemetry.ENABLED:
            telemetry.TENANT_ADAPTER_SWAPS.inc()
            telemetry.TENANT_ADAPTERS_RESIDENT.set(
                bank.stats()["resident"])
        return slot

    def unload_adapter(self, tenant):
        t = self.get(tenant)
        bank = self._need_bank()
        with self._lock:
            slot = bank.slot_of(t.adapter) if t.adapter else -1
            if slot >= 0:
                bank.unload(slot)
            t.adapter = None
        if slot >= 0 and telemetry.ENABLED:
            telemetry.TENANT_ADAPTER_SWAPS.inc()
            telemetry.TENANT_ADAPTERS_RESIDENT.set(
                bank.stats()["resident"])
        return slot

    def slot_for(self, tenant):
        """The bank slot a NEW sequence of ``tenant`` decodes with
        (-1 = base weights only)."""
        t = self._tenants.get(str(tenant))
        if t is None or t.adapter is None or self.bank is None:
            return -1
        return self.bank.slot_of(t.adapter)

    # -- admission protocol (decode scheduler) -------------------------------
    @staticmethod
    def cost_of(prompt_tokens, max_new_tokens):
        """The WFQ charge: the same prompt+generation worst case the
        page reservation pays for."""
        return int(prompt_tokens) + int(max_new_tokens)

    def check_submit(self, tenant, pages_needed):
        """Submit-time gate (raises ``UnknownTenant`` /
        ``TenantQuotaExceeded``); on success charges the tenant's
        waiting share — pair with ``note_dequeue``."""
        t = self.get(tenant)
        with self._lock:
            try:
                self.ledger.check_request(t.name, t.quota, pages_needed)
                self.ledger.check_queue(t.name, t.quota)
            except Exception as exc:
                reason = getattr(exc, "reason", None) or "quota"
                self.rejects[reason] = self.rejects.get(reason, 0) + 1
                if telemetry.ENABLED:
                    telemetry.TENANT_QUOTA_REJECTS.labels(
                        tenant=t.name, reason=reason).inc()
                raise
            self.ledger.enqueue(t.name)
            self.fair.observe_arrival(t.name)
        return t

    def note_dequeue(self, tenant):
        if tenant is None:
            return
        with self._lock:
            self.ledger.dequeue(str(tenant))

    def select(self, waiting, pages_needed):
        """WFQ pick over the scheduler's waiting deque: the request to
        admit next, or None when no backlogged tenant is inside its
        live quota.  ``pages_needed(req)`` is the scheduler's
        reservation estimator."""
        def tenant_of(req):
            return getattr(req, "tenant", None)

        def admit_ok(tname, req):
            if tname is None:
                return True       # base traffic: no tenant quota
            t = self._tenants.get(tname)
            if t is None:
                return True       # registry raced; admit, don't block
            return self.ledger.admissible(tname, t.quota,
                                          pages_needed(req))

        with self._lock:
            picked = self.fair.pick(waiting, tenant_of, admit_ok)
        return None if picked is None else picked[1]

    def admit_granted(self, tenant, cost, pages):
        """The scheduler admitted one sequence: charge the virtual
        clock and reserve the ledger row.  (The waiting share was
        already returned by the scheduler's ``note_dequeue`` — every
        removal from the physical queue reports exactly once.)"""
        if tenant is None:
            # base/anonymous traffic is one pseudo-tenant at the
            # default weight — charged so it cannot starve real
            # tenants, but never quota'd
            with self._lock:
                self.fair.charge(None, cost, self.config.default_weight)
            return
        t = self._tenants.get(str(tenant))
        weight = t.weight if t is not None else self.config.default_weight
        with self._lock:
            self.fair.charge(str(tenant), cost, weight)
            self.ledger.reserve(str(tenant), pages)
        if telemetry.ENABLED:
            telemetry.TENANT_WFQ_PICKS.labels(tenant=str(tenant)).inc()

    def on_release(self, tenant, pages):
        if tenant is None:
            return
        with self._lock:
            self.ledger.release(str(tenant), pages)

    def note_tokens(self, tenant, n=1):
        if tenant is None:
            return
        with self._lock:
            self.served_tokens[tenant] = \
                self.served_tokens.get(tenant, 0) + int(n)

    # -- observability -------------------------------------------------------
    def register_slos(self, ttft_target_s=0.5, q=0.95):
        """One ``mx.obs`` latency objective per registered tenant over
        the tenant-labelled TTFT histogram — the per-tenant SLO view
        (``tenant_ttft:<name>`` in /statz ``slo``)."""
        from ..obs import slo_engine

        names = []
        for t in self.tenants():
            names.append(slo_engine.slo(
                "tenant_ttft:%s" % t.name,
                histogram="tenant_ttft_seconds", q=q,
                target=ttft_target_s,
                labels={"tenant": t.name}).name)
        return names

    def residency(self):
        """The compact per-beat digest fleet discovery publishes: which
        tenants' adapters are resident HERE (router adapter-affinity
        reads this)."""
        bank = self.bank
        resident = []
        with self._lock:
            for t in self._tenants.values():
                if t.adapter is not None and bank is not None and \
                        bank.slot_of(t.adapter) >= 0:
                    resident.append(t.name)
        return {"resident": sorted(resident),
                "slots": bank.n_slots if bank is not None else 0}

    def stats(self):
        with self._lock:
            tenants = {t.name: dict(t.as_dict(),
                                    usage=self.ledger.row(t.name),
                                    served_tokens=self.served_tokens.get(
                                        t.name, 0))
                       for t in self._tenants.values()}
        return {
            "enabled": True,
            "config": self.config.as_dict(),
            "tenants": tenants,
            "wfq": self.fair.snapshot(),
            "rejects": dict(self.rejects),
            "bank": self.bank.stats() if self.bank is not None
            else {"n_slots": 0, "resident": 0, "slots": [],
                  "targets": [], "max_rank": 0, "swaps": 0},
        }
