"""``gordo-components-tpu`` command-line interface.

Reference parity: the ``gordo-components`` click group
(gordo_components/cli/cli.py, unverified; SURVEY.md §2 "cli"):
``build`` (env-var driven builder-pod entrypoint with distinct exit codes),
``run-server``, ``run-watchman``, ``client ...``, ``workflow generate`` —
plus the TPU-native ``build-fleet`` gang entrypoint.
"""

import json
import logging
import os
import sys

import click
import yaml

logger = logging.getLogger(__name__)

EXIT_OK = 0
EXIT_CONFIG_ERROR = 81
EXIT_DATA_ERROR = 82
EXIT_BUILD_ERROR = 83
# partial fleet build: SOME members shipped, the rest are recorded as
# failed in the manifest — distinct from EXIT_BUILD_ERROR so a retry
# controller can tell "rerun just the failures" from "rerun everything"
EXIT_PARTIAL_BUILD = 84


@click.group("gordo-components-tpu")
@click.option("--log-level", default="INFO", envvar="LOG_LEVEL")
@click.option("--platform", default=None, envvar="JAX_PLATFORMS",
              help="Pin the JAX backend (e.g. 'cpu', 'tpu'). Applied "
                   "in-process BEFORE any device use")
@click.option("--profile-dir", default=None, envvar="GORDO_PROFILE_DIR",
              help="Write jax.profiler traces of train/build hot sections "
                   "here (TensorBoard/Perfetto-viewable)")
@click.option("--compile-cache-dir", default=None,
              envvar="GORDO_COMPILE_CACHE_DIR",
              help="Persistent XLA compilation cache (a shared volume in "
                   "pods): restarted/preempted builders and rolling server "
                   "deploys reuse compiled programs instead of compiling "
                   "again. Ignored when JAX_COMPILATION_CACHE_DIR is set "
                   "(JAX then uses that); default <checkout>/.jax_cache")
def gordo(log_level, platform, profile_dir, compile_cache_dir):
    """TPU-native gordo: build, serve, and orchestrate fleets of
    time-series anomaly-detection models."""
    logging.basicConfig(
        level=getattr(logging, log_level.upper(), logging.INFO),
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
    )
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)
    from gordo_components_tpu.utils import resolve_compile_cache

    resolve_compile_cache(compile_cache_dir)
    if profile_dir:
        os.environ["GORDO_PROFILE_DIR"] = profile_dir
    if os.environ.get("GORDO_FAULTS"):
        # chaos runs: arm the named faultpoints before any subsystem runs
        # (resilience/faults.py parks specs for sites not yet imported)
        from gordo_components_tpu.resilience import configure_from_env

        configure_from_env()


def _load_json_or_yaml(value: str):
    try:
        return json.loads(value)
    except json.JSONDecodeError:
        return yaml.safe_load(value)


@gordo.command("build")
@click.option("--name", envvar="MACHINE_NAME", required=True)
@click.option("--model-config", envvar="MODEL_CONFIG", required=True,
              help="JSON/YAML model definition (env MODEL_CONFIG)")
@click.option("--data-config", envvar="DATA_CONFIG", required=True,
              help="JSON/YAML dataset config (env DATA_CONFIG)")
@click.option("--metadata", envvar="METADATA", default="{}")
@click.option("--output-dir", envvar="OUTPUT_DIR", default="./model-output")
@click.option("--model-register-dir", envvar="MODEL_REGISTER_DIR", default=None)
@click.option("--evaluation-config", envvar="EVALUATION_CONFIG", default="{}",
              help="JSON/YAML evaluation block (env EVALUATION_CONFIG): "
                   '{"cv_mode": "full_build"|"cross_val_only", '
                   '"cross_validation": true, "n_splits": 3} — '
                   "TimeSeriesSplit CV scores land in artifact metadata")
@click.option("--print-cv-scores", is_flag=True)
def build(name, model_config, data_config, metadata, output_dir,
          model_register_dir, evaluation_config, print_cv_scores):
    """Build one model (builder-pod entrypoint; reference §3.1)."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.builder import provide_saved_model

    try:
        model_config = _load_json_or_yaml(model_config)
        data_config = _load_json_or_yaml(data_config)
        metadata = _load_json_or_yaml(metadata) or {}
        evaluation_config = _load_json_or_yaml(evaluation_config) or {}
    except yaml.YAMLError as exc:
        click.echo(f"Config parse error: {exc}", err=True)
        sys.exit(EXIT_CONFIG_ERROR)

    try:
        path = provide_saved_model(
            name, model_config, data_config, metadata,
            output_dir=output_dir, model_register_dir=model_register_dir,
            evaluation_config=evaluation_config,
        )
    except (ValueError, ImportError, FileNotFoundError) as exc:
        click.echo(f"Build failed (config/data): {exc}", err=True)
        sys.exit(EXIT_DATA_ERROR)
    except Exception as exc:
        click.echo(f"Build failed: {exc}", err=True)
        sys.exit(EXIT_BUILD_ERROR)

    built_metadata = serializer.load_metadata(path)
    if print_cv_scores:
        cv = built_metadata.get("model", {}).get("cross-validation", {})
        click.echo(json.dumps(cv.get("explained-variance", {})))
    click.echo(path)


@gordo.command("build-fleet")
@click.option("--machines-file", envvar="MACHINES_FILE", required=True,
              help="JSON/YAML file: gang payload or {machines: [...]}")
@click.option("--output-dir", envvar="OUTPUT_DIR", default="./model-output")
@click.option("--model-register-dir", envvar="MODEL_REGISTER_DIR", default=None)
@click.option("--checkpoint-dir", envvar="CHECKPOINT_DIR", default=None,
              help="Enable mid-training preemption recovery for fleet groups")
@click.option("--checkpoint-every", envvar="CHECKPOINT_EVERY", default=1, type=int,
              help="Epochs between fleet checkpoints (amortizes the "
                   "device-to-host state gather for large buckets)")
@click.option("--distributed", is_flag=True, envvar="GORDO_DISTRIBUTED",
              help="Multi-host gang: init jax.distributed and build only "
                   "this host's member slice")
@click.option("--state-dir", envvar="GANG_STATE_DIR", default=None,
              help="Publish gang heartbeats (phase/progress) here for "
                   "watchman to aggregate")
@click.option("--gang-id", envvar="GANG_ID", default=None,
              help="Heartbeat identity (default: hostname-pid)")
def build_fleet_cmd(machines_file, output_dir, model_register_dir, checkpoint_dir,
                    checkpoint_every, distributed, state_dir, gang_id):
    """Build a gang of machines in one process (TPU fleet engine)."""
    from gordo_components_tpu.builder.fleet_build import build_fleet
    from gordo_components_tpu.workflow.config import Machine

    with open(machines_file) as f:
        payload = yaml.safe_load(f)
    if isinstance(payload, dict):
        entries = payload.get("machines", [])
    elif isinstance(payload, list):
        entries = payload
    else:
        entries = []
    machines = []
    for e in entries:
        kwargs = dict(
            name=e["name"],
            dataset=e.get("dataset", {}),
            metadata=e.get("metadata", {}) or {},
            evaluation=e.get("evaluation", {}) or {},
        )
        if e.get("model"):  # absent -> Machine's default model config
            kwargs["model"] = e["model"]
        machines.append(Machine(**kwargs))
    if not machines:
        click.echo("No machines in payload", err=True)
        sys.exit(EXIT_CONFIG_ERROR)
    try:
        results = build_fleet(
            machines, output_dir, model_register_dir=model_register_dir,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            distributed=distributed, state_dir=state_dir, gang_id=gang_id,
        )
    except Exception as exc:
        click.echo(f"Fleet build failed: {exc}", err=True)
        sys.exit(EXIT_BUILD_ERROR)
    # partial-manifest contract (docs/operations.md runbook): the manifest
    # always lists built AND failed members, lands on disk next to the
    # artifacts for the retry controller, and the exit code distinguishes
    # "everything shipped" (0) / "partial — rerun the failed subset" (84)
    # / "nothing shipped" (83)
    manifest = results.manifest()
    try:
        os.makedirs(output_dir, exist_ok=True)
        with open(os.path.join(output_dir, "build_manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2)
    except OSError as exc:
        click.echo(f"warning: could not write build_manifest.json: {exc}", err=True)
    click.echo(json.dumps(manifest, indent=2))
    if results.failed and not results:
        sys.exit(EXIT_BUILD_ERROR)
    if results.failed:
        sys.exit(EXIT_PARTIAL_BUILD)


@gordo.command("checkpoint-prune")
@click.option("--checkpoint-dir", envvar="CHECKPOINT_DIR", required=True)
@click.option("--older-than-days", default=7.0, type=float,
              help="Delete bucket checkpoints untouched for this long")
def checkpoint_prune_cmd(checkpoint_dir, older_than_days):
    """Explicit janitor for stranded fleet checkpoints (checkpoints whose
    config/data key will never be computed again accumulate forever on a
    shared volume; pruning is deliberately NOT a side effect of builds)."""
    from gordo_components_tpu.parallel.checkpoint import prune_stale_checkpoints

    n = prune_stale_checkpoints(checkpoint_dir, older_than_days)
    click.echo(f"Pruned {n} stale checkpoint(s)")


@gordo.command("run-server")
@click.option("--model-dir", envvar="MODEL_COLLECTION_DIR", required=True)
@click.option("--host", default="0.0.0.0", envvar="SERVER_HOST")
@click.option("--port", default=5555, envvar="SERVER_PORT", type=int)
@click.option(
    "--devices", default=None, type=int, envvar="GORDO_SERVER_DEVICES",
    help="Shard the model bank over an N-device models-axis mesh "
    "(0/unset = all available devices when more than one is present).",
)
def run_server_cmd(model_dir, host, port, devices):
    """Serve the model collection under MODEL_COLLECTION_DIR."""
    from gordo_components_tpu.server import run_server

    run_server(model_dir, host=host, port=port, devices=devices)


@gordo.command("run-watchman")
@click.option("--project", envvar="PROJECT_NAME", required=True)
@click.option("--server-base-url", envvar="SERVER_BASE_URL", required=True)
@click.option("--targets", envvar="TARGET_NAMES", default=None,
              help="JSON list; discovered from the server when omitted")
@click.option("--gang-state-dir", envvar="GANG_STATE_DIR", default=None,
              help="Aggregate builder-gang heartbeats from this directory")
@click.option("--full-metadata", is_flag=True, envvar="WATCHMAN_FULL_METADATA",
              help="Aggregate FULL per-target metadata instead of the "
                   "bounded digest (digest keeps 10k-fleet snapshots under "
                   "~1 MB; full restores the reference-style aggregate)")
@click.option("--host", default="0.0.0.0")
@click.option("--port", default=5556, type=int)
def run_watchman_cmd(project, server_base_url, targets, gang_state_dir,
                     full_metadata, host, port):
    """Fleet health aggregation service."""
    from gordo_components_tpu.watchman import run_watchman

    target_list = json.loads(targets) if targets else None
    run_watchman(
        project, server_base_url, target_list, host=host, port=port,
        gang_state_dir=gang_state_dir, full_metadata=full_metadata,
    )


@gordo.group("client")
def client_group():
    """Bulk prediction client."""


@client_group.command("predict")
@click.argument("start")
@click.argument("end")
@click.option("--project", envvar="PROJECT_NAME", required=True)
@click.option("--base-url", default="http://localhost:5555")
@click.option("--target", multiple=True, help="Limit to specific machines")
@click.option("--parquet-dir", default=None, help="Forward results to parquet files")
@click.option("--batch-size", default=1000, type=int)
@click.option("--body-encoding", type=click.Choice(["auto", "json", "parquet"]),
              default="auto", envvar="GORDO_CLIENT_ENCODING",
              help="Scoring POST body encoding: auto negotiates parquet "
                   "when the server advertises it (2.3x JSON throughput "
                   "measured), json/parquet force one")
def client_predict(start, end, project, base_url, target, parquet_dir,
                   batch_size, body_encoding):
    """Bulk anomaly scoring over a time range."""
    import pandas as pd

    from gordo_components_tpu.client import Client, ForwardPredictionsIntoParquet

    forwarder = ForwardPredictionsIntoParquet(parquet_dir) if parquet_dir else None
    use_parquet = {"auto": "auto", "json": False, "parquet": True}[body_encoding]
    client = Client(
        project, base_url=base_url, forwarder=forwarder, batch_size=batch_size,
        use_parquet=use_parquet,
    )
    results = client.predict(
        pd.Timestamp(start), pd.Timestamp(end), targets=list(target) or None
    )
    ok = sum(1 for r in results if r.ok)
    click.echo(f"{ok}/{len(results)} machines scored successfully")
    for r in results:
        if not r.ok:
            click.echo(f"  FAILED {r.name}: {r.error_messages[:1]}", err=True)
    if ok < len(results):
        sys.exit(1)


@client_group.command("metadata")
@click.option("--project", envvar="PROJECT_NAME", required=True)
@click.option("--base-url", default="http://localhost:5555")
def client_metadata(project, base_url):
    """Print every model's metadata as JSON."""
    import asyncio

    import aiohttp

    from gordo_components_tpu.client.io import fetch_json, fetch_metadata_all

    async def go():
        async with aiohttp.ClientSession() as session:
            # one metadata-all request against a collection server;
            # per-target fetches only for foreign servers
            batched = await fetch_metadata_all(session, base_url, project)
            if batched is not None:
                return {
                    name: entry.get("endpoint-metadata", {})
                    for name, entry in batched["targets"].items()
                    # a catch-all proxy can pass the shape check with
                    # non-dict entries; skip them rather than crash
                    if isinstance(entry, dict)
                }
            targets = (
                await fetch_json(session, f"{base_url}/gordo/v0/{project}/models")
            )["models"]
            out = {}
            for t in targets:
                body = await fetch_json(
                    session, f"{base_url}/gordo/v0/{project}/{t}/metadata"
                )
                out[t] = body.get("endpoint-metadata", {})
            return out

    click.echo(json.dumps(asyncio.run(go()), indent=2, default=str))


@client_group.command("download-model")
@click.argument("target")
@click.argument("dest", type=click.Path())
@click.option("--project", envvar="PROJECT_NAME", required=True)
@click.option("--base-url", default="http://localhost:5555")
def client_download_model(target, dest, project, base_url):
    """Download a model artifact as a pickle file."""
    import requests

    resp = requests.get(
        f"{base_url}/gordo/v0/{project}/{target}/download-model", timeout=120
    )
    resp.raise_for_status()
    with open(dest, "wb") as f:
        f.write(resp.content)
    click.echo(dest)


@gordo.group("workflow")
def workflow_group():
    """Workflow generation."""


@workflow_group.command("compile")
@click.option("--machine-config", "-f", required=True, type=click.Path(exists=True))
@click.option("--project-name", "-p", required=True)
@click.option("--output-file", "-o", default=None, type=click.Path())
@click.option("--models-per-bucket", default=None, type=int)
@click.option("--devices-per-bucket", default=None, type=int)
def workflow_compile(machine_config, project_name, output_file,
                     models_per_bucket, devices_per_bucket):
    """Compile a fleet spec into the typed build/place/canary/promote
    DAG (deterministic JSON — the reviewed rollout artifact)."""
    from gordo_components_tpu.workflow import compile_fleet

    overrides = {}
    if models_per_bucket:
        overrides["models_per_bucket"] = models_per_bucket
    if devices_per_bucket:
        overrides["devices_per_bucket"] = devices_per_bucket
    try:
        with open(machine_config) as f:
            dag = compile_fleet(yaml.safe_load(f), project_name, **overrides)
    except (ValueError, yaml.YAMLError) as exc:
        click.echo(f"Invalid fleet spec: {exc}", err=True)
        sys.exit(EXIT_CONFIG_ERROR)
    doc = dag.to_json()
    if output_file:
        with open(output_file, "w") as f:
            f.write(doc + "\n")
        click.echo(output_file)
    else:
        click.echo(doc)


@workflow_group.command("run")
@click.option("--machine-config", "-f", required=True, type=click.Path(exists=True))
@click.option("--project-name", "-p", required=True)
@click.option("--state-dir", envvar="GORDO_FLEET_STATE_DIR",
              default=".fleet-state",
              help="Executor state (step keys, artifacts, incumbent "
                   "backups); re-runs execute only the stale subgraph")
@click.option("--server-url", envvar="SERVER_BASE_URL", default=None,
              help="Live replica to roll the fleet onto (canary + "
                   "promote through its zero-downtime /reload swap); "
                   "omitted = plan-only run (build + plan, no landing)")
@click.option("--collection-dir", envvar="MODEL_COLLECTION_DIR", default=None,
              help="The live server's artifact dir (required with "
                   "--server-url)")
@click.option("--model-register-dir", envvar="MODEL_REGISTER_DIR", default=None)
def workflow_run(machine_config, project_name, state_dir, server_url,
                 collection_dir, model_register_dir):
    """Compile AND execute a fleet spec: build -> bucket -> place ->
    canary -> promote, goodput-judged with auto-rollback."""
    from gordo_components_tpu.workflow import FleetExecutor, compile_fleet

    try:
        with open(machine_config) as f:
            dag = compile_fleet(yaml.safe_load(f), project_name)
        executor = FleetExecutor(
            dag, state_dir, server_url=server_url,
            collection_dir=collection_dir, register_dir=model_register_dir,
        )
    except (ValueError, yaml.YAMLError) as exc:
        click.echo(f"Invalid fleet spec: {exc}", err=True)
        sys.exit(EXIT_CONFIG_ERROR)
    report = executor.run()
    click.echo(json.dumps(report, indent=2, default=str))
    if report["failed"]:
        sys.exit(
            EXIT_PARTIAL_BUILD if report["executed"] else EXIT_BUILD_ERROR
        )


@workflow_group.command("generate")
@click.option("--machine-config", "-f", required=True, type=click.Path(exists=True))
@click.option("--project-name", "-p", required=True)
@click.option("--output-file", "-o", default=None, type=click.Path())
@click.option("--models-per-gang", default=None, type=int)
@click.option("--devices-per-gang", default=None, type=int)
def workflow_generate(machine_config, project_name, output_file, models_per_gang, devices_per_gang):
    """Render gang-scheduled TPU manifests from a fleet config
    (reference §3.4)."""
    from gordo_components_tpu.workflow import NormalizedConfig, generate_workflow

    try:
        config = NormalizedConfig.from_yaml_file(machine_config)
    except (ValueError, yaml.YAMLError) as exc:
        click.echo(f"Invalid machine config: {exc}", err=True)
        sys.exit(EXIT_CONFIG_ERROR)
    overrides = {}
    if models_per_gang:
        overrides["models_per_gang"] = models_per_gang
    if devices_per_gang:
        overrides["devices_per_gang"] = devices_per_gang
    try:
        # generation now compiles the spec (fleet compiler validation
        # included), so spec errors surface here too — same clean exit
        # as `workflow compile` on the identical spec
        manifest = generate_workflow(config, project_name, **overrides)
    except ValueError as exc:
        click.echo(f"Invalid fleet spec: {exc}", err=True)
        sys.exit(EXIT_CONFIG_ERROR)
    if output_file:
        with open(output_file, "w") as f:
            f.write(manifest)
        click.echo(output_file)
    else:
        click.echo(manifest)


if __name__ == "__main__":
    gordo()
