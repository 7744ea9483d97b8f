"""Device-resident MD inner loop.

The reference steps MD from the host: every step pays a host->device
round-trip plus a full graph rebuild (reference pes.py:68-85 — its
`Distributed.create_distributed` runs per call). Here, with skin-radius
graph reuse, the velocity-Verlet integrator itself runs ON DEVICE inside
one jitted ``lax.while_loop``: positions, velocities, and forces stay
resident.

Two chunk steppers exist:

- **device-rebuild** (default for single-partition, non-bond-graph
  potentials): when the Verlet criterion fires, the neighbor graph is
  rebuilt ON DEVICE inside the loop body (``neighbors.device``'s cell-list
  search + ``partition.refresh_edges``) and integration continues — a
  trajectory of N steps runs as ONE device program with zero host syncs
  except the telemetry flush at chunk end. Same sticky caps => same shapes
  => the rebuild never re-traces; a capacity bust (cell or edge overflow)
  stops the loop and falls back to a host rebuild with grown caps.
- **host-rebuild** (multi-partition, bond-graph models, or
  ``DISTMLIP_DEVICE_REBUILD=0``): the historical path — the loop
  self-terminates when any owned atom has moved more than skin/2 from its
  graph-build position, and the host rebuilds between chunks.

Optional Berendsen velocity-rescale thermostatting (global temperature via
psum across the mesh) covers NVT; NVE is the default.
"""

from __future__ import annotations

import numpy as np

from .atoms import AMU_A2_FS2_TO_EV, KB, Atoms


def _make_chunk_stepper(total_energy, dt: float, skin: float):
    """Jitted (params, graph, pos, ref, vel, masses, n_steps, taut, t0) ->
    (pos, vel, forces, steps_done, energy, kinetic): up to n_steps
    velocity-Verlet steps on device. A step whose trial positions would
    leave the skin/2 validity radius of the reused neighbor list — measured
    against ``ref``, the positions the graph was BUILT at, not chunk start,
    so a warm cache can't double-spend the drift budget — is NOT committed
    (no force evaluation with a stale list ever reaches the returned
    state); the loop stops and the host rebuilds."""
    import jax
    import jax.numpy as jnp

    def forces_of(params, graph, pos):
        e, g = jax.value_and_grad(total_energy, argnums=2)(
            params, graph, pos, jnp.zeros((3, 3), dtype=pos.dtype)
        )
        return e, -g

    @jax.jit
    def run_chunk(params, graph, pos, ref, vel, masses, n_steps, taut, t0):
        dtype = pos.dtype
        owned = graph.owned_mask[..., None].astype(dtype)
        inv_m = owned / (masses[..., None] * AMU_A2_FS2_TO_EV)
        # 3N - 3 translational-projected dof, matching Atoms.temperature
        n_dof = jnp.maximum(
            3.0 * graph.n_total_nodes.astype(dtype) - 3.0, 1.0
        )
        e0, f0 = forces_of(params, graph, pos)
        half = (0.5 * skin) ** 2

        def kinetic(vel):
            # sum over owned rows across the whole mesh (stacked layout)
            return 0.5 * jnp.sum(
                masses[..., None] * owned * vel * vel
            ) * AMU_A2_FS2_TO_EV

        def cond(state):
            *_, steps, _e, stop = state
            return (steps < n_steps) & ~stop

        def body(state):
            pos_c, vel_c, f_c, steps, e_c, _ = state
            vel_h = vel_c + (0.5 * dt) * f_c * inv_m
            pos_n = pos_c + dt * vel_h * owned
            disp = (pos_n - ref) * owned
            exceed = jnp.max(jnp.sum(disp * disp, axis=-1)) >= half

            def commit(_):
                e_n, f_n = forces_of(params, graph, pos_n)
                vel_n = vel_h + (0.5 * dt) * f_n * inv_m
                # Berendsen rescale toward t0 (taut <= 0 disables); lambda
                # clipped like the host thermostat (md.py) so cold starts
                # don't blow up
                temp = 2.0 * kinetic(vel_n) / (n_dof * KB)
                lam = jnp.where(
                    taut > 0.0,
                    jnp.clip(
                        jnp.sqrt(jnp.maximum(
                            1.0
                            + (dt / taut) * (t0 / jnp.maximum(temp, 1e-12) - 1.0),
                            0.0,
                        )),
                        0.9, 1.1,
                    ),
                    1.0,
                )
                return (pos_n, vel_n * lam.astype(dtype), f_n, steps + 1,
                        e_n, jnp.bool_(False))

            def stop(_):
                return (pos_c, vel_c, f_c, steps, e_c, jnp.bool_(True))

            return jax.lax.cond(exceed, stop, commit, None)

        state = (pos, vel, f0, jnp.zeros((), jnp.int32), e0, jnp.bool_(False))
        pos_f, vel_f, f_f, steps, e_f, _ = jax.lax.while_loop(cond, body, state)
        return pos_f, vel_f, f_f, steps, e_f, kinetic(vel_f)

    return run_chunk


def _make_device_rebuild_stepper(total_energy, dt: float, skin: float,
                                 spec_static, spec_arrays):
    """Chunk stepper with the neighbor rebuild FOLDED INTO the loop body.

    When a trial step exceeds the skin/2 drift budget, the loop rebuilds
    the neighbor list on device (``cell_list_neighbors``), swaps the edge
    arrays into the carried graph (``refresh_edges`` — same static shapes,
    no re-trace), resets the drift reference to the rebuild positions and
    COMMITS the step with the fresh list. The only early exit besides step
    count is a capacity overflow (cell or edge), which returns without
    committing the offending step so the host can rebuild with grown caps
    and resume exactly where the device left off.

    Returns ``(graph, ref, pos, vel, steps_done, energy, kinetic,
    overflow, rebuilds, edges_needed)`` — ``edges_needed`` is the true
    candidate count of the overflowing rebuild (0 if none), letting the
    host grow the right capacity.
    """
    import jax
    import jax.numpy as jnp

    from ..neighbors.device import cell_list_neighbors
    from ..partition.graph import refresh_edges

    spec_arrays = {k: jnp.asarray(v) for k, v in spec_arrays.items()}

    def forces_of(params, graph, pos):
        e, g = jax.value_and_grad(total_energy, argnums=2)(
            params, graph, pos, jnp.zeros((3, 3), dtype=pos.dtype)
        )
        return e, -g

    @jax.jit
    def run_chunk(params, graph, pos, ref, vel, masses, n_steps, taut, t0):
        dtype = pos.dtype
        owned = graph.owned_mask[..., None].astype(dtype)
        inv_m = owned / (masses[..., None] * AMU_A2_FS2_TO_EV)
        n_dof = jnp.maximum(
            3.0 * graph.n_total_nodes.astype(dtype) - 3.0, 1.0
        )
        e0, f0 = forces_of(params, graph, pos)
        half = (0.5 * skin) ** 2

        def kinetic(vel):
            return 0.5 * jnp.sum(
                masses[..., None] * owned * vel * vel
            ) * AMU_A2_FS2_TO_EV

        def cond(state):
            steps, stop = state[5], state[7]
            return (steps < n_steps) & ~stop

        def body(state):
            (g_c, ref_c, pos_c, vel_c, f_c, steps, e_c, _stop,
             n_reb, ne_need) = state
            vel_h = vel_c + (0.5 * dt) * f_c * inv_m
            pos_n = pos_c + dt * vel_h * owned
            disp = (pos_n - ref_c) * owned
            exceed = jnp.max(jnp.sum(disp * disp, axis=-1)) >= half

            def do_rebuild(_):
                src, dstn, off, ne, ovf = cell_list_neighbors(
                    spec_static, spec_arrays, pos_n[0])
                g2 = refresh_edges(g_c, src, dstn, off.astype(dtype), ne)
                return g2, pos_n, ovf, n_reb + 1, ne

            def keep(_):
                return g_c, ref_c, jnp.bool_(False), n_reb, ne_need

            g2, ref2, ovf, n_reb2, ne2 = jax.lax.cond(
                exceed, do_rebuild, keep, None)

            def overflow(_):
                # capacity bust: do NOT commit the step — the host rebuilds
                # with grown caps and the trajectory resumes from pos_c.
                # The overflowing rebuild's results are discarded, so it is
                # NOT counted (n_reb, not n_reb2): telemetry's on-device
                # tally covers rebuilds that actually served steps.
                return (g_c, ref_c, pos_c, vel_c, f_c, steps, e_c,
                        jnp.bool_(True), n_reb, ne2)

            def commit(_):
                e_n, f_n = forces_of(params, g2, pos_n)
                vel_n = vel_h + (0.5 * dt) * f_n * inv_m
                temp = 2.0 * kinetic(vel_n) / (n_dof * KB)
                lam = jnp.where(
                    taut > 0.0,
                    jnp.clip(
                        jnp.sqrt(jnp.maximum(
                            1.0
                            + (dt / taut) * (t0 / jnp.maximum(temp, 1e-12) - 1.0),
                            0.0,
                        )),
                        0.9, 1.1,
                    ),
                    1.0,
                )
                return (g2, ref2, pos_n, vel_n * lam.astype(dtype), f_n,
                        steps + 1, e_n, jnp.bool_(False), n_reb2, ne2)

            return jax.lax.cond(ovf, overflow, commit, None)

        zero = jnp.zeros((), jnp.int32)
        state = (graph, ref, pos, vel, f0, zero, e0, jnp.bool_(False),
                 zero, zero)
        (g_f, ref_f, pos_f, vel_f, _f, steps, e_f, stopped,
         n_reb, ne_need) = jax.lax.while_loop(cond, body, state)
        return (g_f, ref_f, pos_f, vel_f, steps, e_f, kinetic(vel_f),
                stopped, n_reb, ne_need)

    return run_chunk


class DeviceMD:
    """Chunked device-resident MD driver over a DistPotential.

    Usage::

        pot = DistPotential(model, params, skin=1.0)
        md = DeviceMD(pot, atoms, timestep=1.0)          # NVE
        md = DeviceMD(pot, atoms, timestep=1.0,
                      temperature=300.0, taut=100.0)     # Berendsen NVT
        md.run(1000)

    For single-partition, non-bond-graph potentials the neighbor rebuild
    itself runs ON DEVICE inside the chunk loop — the whole trajectory is
    device-resident and the host only sees telemetry.
    ``device_rebuild="auto"`` inherits the potential's ``device_rebuild``
    setting; an explicit True/False here overrides it. Otherwise
    (multi-partition meshes, CHGNet's bond graph, or
    ``DISTMLIP_DEVICE_REBUILD=0``) the graph is rebuilt on the host when
    the skin criterion fires inside the device loop. Requires
    ``pot.skin > 0`` (the reuse radius defines the rebuild criterion).

    ``cell_capacity`` pins the device cell-list's atoms-per-cell capacity
    (testing/tuning; default: estimated from the first build with slack and
    grown automatically after an overflow fallback).
    """

    def __init__(self, potential, atoms: Atoms, timestep: float = 1.0,
                 temperature: float | None = None, taut: float = 100.0,
                 device_rebuild: bool | str = "auto",
                 cell_capacity: int | None = None,
                 telemetry=None):
        from ..neighbors.device import device_rebuild_enabled
        from ..parallel.runtime import make_total_energy

        if potential.skin <= 0.0:
            raise ValueError("DeviceMD requires DistPotential(skin > 0)")
        if telemetry is not None:
            getattr(potential, "attach_telemetry", lambda t: None)(telemetry)
        potential.ensure_runtime(atoms)  # AUTO partitioning needs the cell
        self.pot = potential
        self.atoms = atoms
        self.dt = float(timestep)
        self.temperature = temperature
        self.taut = float(taut) if temperature is not None else 0.0
        self._total_energy = make_total_energy(
            potential.model.energy_fn, potential.mesh,
            # inherit the potential's Pallas routing; the MD force program
            # differentiates positions only, so the force-program policy
            # applies (no weight cotangents riding the scan carry / mesh)
            kernels=getattr(potential, "kernels", None),
            kernels_diff_params=False,
        )
        if device_rebuild == "auto":
            # inherit the potential's opt-out (an explicit True/False to
            # DeviceMD overrides it)
            device_rebuild = bool(getattr(potential, "device_rebuild", True))
        self.device_rebuild = bool(
            device_rebuild
            and device_rebuild_enabled()
            and potential.num_partitions == 1
            and not potential.use_bond_graph)
        self._stepper = _make_chunk_stepper(
            self._total_energy, self.dt, potential.skin
        )
        self._dev_stepper = None
        self._spec = None
        self._spec_key = None
        self._cell_capacity = cell_capacity
        self._cell_cap_floor = 4
        self.steps_done = 0
        self.rebuilds = 0             # host graph builds used
        self.rebuilds_on_device = 0   # in-loop device rebuilds
        self.rebuild_overflows = 0    # device-capacity busts -> host fallback
        self.energies: list[float] = []
        self.results: dict = {"energy": None, "kinetic": 0.0}

    def _ensure_spec(self, graph) -> None:
        """(Re)build the device cell-list spec + stepper when the graph's
        capacity bucket changes (host rebuild grew caps) or on first use.
        Same spec statics => the jitted stepper is reused: compile count
        stays flat across rebuilds."""
        from ..neighbors.device import build_cell_list_spec

        pot, atoms = self.pot, self.atoms
        key = (graph.n_cap, graph.e_cap, self._cell_capacity,
               self._cell_cap_floor)
        if self._spec is not None and self._spec_key == key:
            return
        r_build = pot.cutoff + pot.skin
        static, arrays = build_cell_list_spec(
            atoms.cell, atoms.pbc, r_build, len(atoms), graph.n_cap,
            graph.e_cap, positions=atoms.positions,
            cell_cap=self._cell_capacity,
            min_cell_cap=self._cell_cap_floor,
            dtype=np.asarray(graph.lattice).dtype,
        )
        self._spec = (static, arrays)
        self._spec_key = key
        self._dev_stepper = _make_device_rebuild_stepper(
            self._total_energy, self.dt, pot.skin, static, arrays)

    def _grow_caps_after_overflow(self, edges_needed: int, e_cap: int,
                                  cell_cap: int) -> None:
        """Grow whichever capacity busted (shared policy with
        DistPotential); the next host rebuild — and the spec keyed on its
        caps — picks the new sizes up."""
        from ..neighbors.device import grow_caps_after_overflow

        new_floor = grow_caps_after_overflow(
            self.pot.caps, edges_needed, e_cap, cell_cap,
            self._cell_cap_floor)
        if new_floor != self._cell_cap_floor:
            self._cell_cap_floor = new_floor
            self._cell_capacity = None  # an explicit pin is outgrown

    def run(self, steps: int, max_chunk: int | None = None) -> None:
        import jax
        import jax.numpy as jnp

        import time

        pot, atoms = self.pot, self.atoms
        remaining = int(steps)
        if remaining <= 0:
            return
        max_chunk = int(max_chunk or steps)
        overflow_stalls = 0
        while remaining > 0:
            t_chunk = time.perf_counter()
            graph, host, positions = pot._prepare(atoms)
            # fresh = built at the CURRENT positions this call; cache hits
            # AND adopted background prefetches arrive with Verlet budget
            # already spent, so a rebuild-count delta (which counts both
            # kinds of used graph) cannot distinguish them. A fresh build
            # may itself have run ON DEVICE (the potential's refresh) —
            # attribute it to the right tally or the host/device split in
            # telemetry (and bench's device_md_rebuilds_*) lies.
            fresh = pot.last_build_fresh
            fresh_on_device = bool(
                pot._prepare_flags.get("rebuild_on_device"))
            self.rebuilds += int(fresh and not fresh_on_device)
            self.rebuilds_on_device += int(fresh and fresh_on_device)
            dtype = np.asarray(graph.lattice).dtype
            # skin criterion reference = the positions the graph was BUILT
            # at (cache slot 3); on a fresh build this equals the current
            # positions, on a warm cache it charges drift already spent
            ref = host.scatter_global(
                pot._cache[3].astype(dtype), graph.n_cap
            )
            vel = host.scatter_global(
                atoms.velocities.astype(dtype), graph.n_cap
            )
            masses = host.scatter_global(
                atoms.masses.astype(dtype), graph.n_cap, fill=1.0
            )
            n = jnp.int32(min(remaining, max_chunk))
            if self.device_rebuild:
                self._ensure_spec(graph)
                t_dev = time.perf_counter()
                (g_f, ref_f, pos_f, vel_f, done, e_f, ke, ovf, n_reb,
                 ne_need) = self._dev_stepper(
                    pot.params, graph, positions, ref, vel, masses, n,
                    jnp.float32(self.taut),
                    jnp.float32(self.temperature or 0.0),
                )
                done = int(done)  # blocks on the chunk; device_s is real
                t_done = time.perf_counter()
                n_reb = int(n_reb)
                overflow = bool(ovf)
                self.rebuilds_on_device += n_reb
                atoms.positions = host.gather_owned(
                    np.asarray(pos_f, dtype=np.float64), len(atoms))
                atoms.velocities = host.gather_owned(
                    np.asarray(vel_f, dtype=np.float64), len(atoms))
                if n_reb:
                    # the carried graph was refreshed in-loop: swap it into
                    # the potential's skin cache with ITS build positions so
                    # the next chunk (or a later calculate()) reuses it
                    pot._install_refreshed(
                        g_f, host.gather_owned(
                            np.asarray(ref_f, dtype=np.float64), len(atoms)))
                if done:
                    self.energies.append(float(e_f))
                    self.steps_done += done
                    remaining -= done
                    self.results = {"energy": self.energies[-1],
                                    "kinetic": float(ke)}
                    # the stall guard tracks CONSECUTIVE zero-progress
                    # overflows only — any committed step resets it
                    overflow_stalls = 0
                if overflow:
                    self.rebuild_overflows += 1
                    spec_static = self._spec[0]
                    self._grow_caps_after_overflow(
                        int(ne_need), graph.e_cap, spec_static.cell_cap)
                    pot._cache = None  # host rebuild at current positions
                    if not done:
                        overflow_stalls += 1
                        if overflow_stalls > 4:
                            raise RuntimeError(
                                "device neighbor rebuild overflowed "
                                "repeatedly without progress; capacities "
                                "are not converging")
                pot._emit_record(
                    "md_chunk", host,
                    total_s=time.perf_counter() - t_chunk,
                    extra_timings={"device_s": t_done - t_dev},
                    cache_size_fn=getattr(self._dev_stepper, "_cache_size",
                                          None),
                    steps_done=done, steps_total=self.steps_done,
                    rebuild_count=n_reb + int(fresh),
                    rebuild_on_device=(n_reb
                                       + int(fresh and fresh_on_device)),
                    rebuild_overflow_count=self.rebuild_overflows,
                    chunk_overflow=overflow)
                continue
            t_dev = time.perf_counter()
            pos_f, vel_f, f_f, done, e_f, ke = self._stepper(
                pot.params, graph, positions, ref, vel, masses, n,
                jnp.float32(self.taut),
                jnp.float32(self.temperature or 0.0),
            )
            done = int(done)  # blocks on the chunk; device_s is real
            t_done = time.perf_counter()

            def emit_chunk(**extra):
                pot._emit_record(
                    "md_chunk", host,
                    total_s=time.perf_counter() - t_chunk,
                    extra_timings={"device_s": t_done - t_dev},
                    cache_size_fn=getattr(self._stepper, "_cache_size", None),
                    steps_done=done, steps_total=self.steps_done,
                    rebuild_count=int(fresh),
                    rebuild_on_device=int(fresh and fresh_on_device),
                    **extra)
            if done == 0:
                # record the wasted dispatch either way: repeated
                # zero-progress retries are exactly the pathology
                # telemetry exists to surface
                emit_chunk(zero_progress=True, fresh_build=fresh)
                if not fresh:
                    # warm cache arrived with most of the skin budget spent;
                    # rebuild at the current positions and retry (in place
                    # on device when the potential supports it)
                    pot._mark_cache_stale()
                    continue
                # fresh build: criterion reference == current positions, so
                # a zero-step chunk means one dt exceeds skin/2 — retrying
                # cannot help
                raise RuntimeError(
                    "device MD chunk made no progress; increase skin"
                )
            atoms.positions = host.gather_owned(
                np.asarray(pos_f, dtype=np.float64), len(atoms)
            )
            atoms.velocities = host.gather_owned(
                np.asarray(vel_f, dtype=np.float64), len(atoms)
            )
            if done < int(n):
                # chunk stopped on the skin criterion: the cached graph's
                # drift budget is exhausted — invalidate it so the next
                # chunk (or the next pot.calculate) rebuilds instead of
                # paying a null device dispatch to find out. On a device-
                # refresh-capable potential the graph itself is KEPT and
                # the rebuild happens in place on the chip.
                pot._mark_cache_stale()
            self.energies.append(float(e_f))
            self.steps_done += done
            remaining -= done
            # one record per device chunk: device_s covers the whole jitted
            # while_loop (`done` steps), so mean per-step cost is
            # device_s / steps_done
            emit_chunk()
            self.results = {"energy": self.energies[-1], "kinetic": float(ke)}
